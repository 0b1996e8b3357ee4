#!/usr/bin/env python3
"""The controls of `correct`: the reference put in the program's place with
one thing a later PR could be tempted to do, which has to come out as NOT
correct, and beside them the readings of what `correct` cannot see.

The configurations state exact 64-bit integer arithmetic (memory in bytes,
fractions in millionths) and two guarantees on the order of work. Controls
(each differs from the reference on nearly every placement, on every seed):

- `int32`: the score path with every quantity held and multiplied in 32 bits,
  as a narrower kernel would: 256Gi in bytes wraps to 0 and the millionths
  overflow;
- `stale_batch`: a batch of 64 pods scored against the usage the batch started
  with (the sequential carry left out: the guarantee "identical to sequential
  scheduling in creation order" broken);
- `last_maximum`: ties broken the other way (the guarantee "first maximum in
  walk order" broken);
- `node_add_ignored`, `node_delete_ignored`, `tree_order_stale`: the three
  things a cache can get wrong about a cluster event (the node never joins;
  the removed node's pods still count and its row still takes pods; the new
  node put at the end of the flat list and not into its zone's turn). They
  are read on a log WITH events (`event_log`: init pods, a third of the
  measured pods, a node removed, a third, a node added to its zone, the
  rest, one pod no node can hold, then deletes and creates), since the plain
  log has none;
- `<key>.<name>`: for each pod feature that the configuration's templates use,
  the controls its own file states (`reference_features/<key>.py`,
  `CONTROLS`): the feature's state with one guarantee broken, put in its
  place. Found by name: this file names no feature.

- over a log that preempts (`--preemption`, `preemption_log`: the init pods,
  then the measured pods in 24 parts, before each part one pod of the
  configuration's `preemptors` or `churn.pod` templates in turn, its victims
  deleted where the reference evicts them, and after the part its `retry`),
  the same controls, held to placements, evictions and nominations alike. A
  pod feature's controls are looked for in EVERY pod template of the
  configuration, the churn and preemptor templates too, and the plain log
  creates one pod of each such template, which may pend, a third into the
  wave.

Reading, NOT a control: `float32`, the score terms in float32 and floored
where the reference floors. On the uniform clusters (equal nodes, equal
pods, power-of-two sizes) it places every pod where int64 does, so exact
placements do not detect float32 scoring there, and `correct` would pass it. A
later PR that lowers the score path's precision must bring a configuration
with unequal nodes or pods on which this reading differs: node groups of
several sizes (`tests/benchmark/toy_bench/configs/unequal-toy.json`; PERF.md).

    python3 benchmark/control.py --config spread-5k --seeds 11 12 13
    python3 benchmark/control.py --config spread-5k --seeds 11 12 13 --events
    python3 benchmark/control.py --bench-dir tests/benchmark/toy_bench \
        --config preempt-toy --seeds 11 12 13 --preemption
    python3 benchmark/control.py --bench-dir tests/benchmark/toy_bench \
        --config unequal-toy --seeds 11 12 13

schedules the init pods and one wave at the configuration's own size with the
reference and with each control, and prints how many placements differ (the
limit of the comparison is 0 differing; a control must differ); `--events`
does the same over the log with cluster events, with the event controls
beside the others. It needs no chip: both sides are numpy.
`tests/benchmark/test_benchmark_reference.py` and `test_benchmark_events.py`
keep it as tests at toy size.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import features  # noqa: E402
import objects  # noqa: E402
import reference  # noqa: E402
from reference import FRACTION_SCALE, MAX_NODE_SCORE  # noqa: E402


class Float32Scores(reference.Reference):
    """The same score terms, floored where the reference floors them, with
    every quantity and operation in float32."""

    def resource_scores(self, shape, rows):
        f = np.float32
        a_cpu, a_mem = self.alloc_cpu[rows].astype(f), self.alloc_mem[rows].astype(f)
        u_cpu = (self.nz_cpu[rows] + shape.nz_cpu).astype(f)
        u_mem = (self.nz_mem[rows] + shape.nz_memory).astype(f)
        hundred, scale = f(MAX_NODE_SCORE), f(FRACTION_SCALE)
        l_cpu = np.floor((a_cpu - u_cpu) * hundred / a_cpu)
        l_mem = np.floor((a_mem - u_mem) * hundred / a_mem)
        least = np.floor((l_cpu + l_mem) / f(2))
        q_cpu = np.floor(u_cpu * scale / a_cpu)
        q_mem = np.floor(u_mem * scale / a_mem)
        balanced = np.floor((hundred * scale - f(50) * np.abs(q_cpu - q_mem))
                            / scale)
        return (least + balanced).astype(np.int64)


class Int32Scores(reference.Reference):
    """The same score terms with every quantity held, and every product
    taken, in 32-bit integers (wrapping, as the hardware would); a division
    by a capacity that wrapped to 0 gives 0."""

    def resource_scores(self, shape, rows):
        i = np.int32
        with np.errstate(all="ignore"):
            a_cpu = self.alloc_cpu[rows].astype(i)
            a_mem = self.alloc_mem[rows].astype(i)
            u_cpu = (self.nz_cpu[rows] + shape.nz_cpu).astype(i)
            u_mem = (self.nz_mem[rows] + shape.nz_memory).astype(i)
            hundred, scale = i(MAX_NODE_SCORE), i(FRACTION_SCALE)

            def div(x, y):
                return np.where(y == 0, i(0), x // np.where(y == 0, i(1), y))

            l_cpu = np.where(u_cpu > a_cpu, i(0),
                             div((a_cpu - u_cpu) * hundred, a_cpu))
            l_mem = np.where(u_mem > a_mem, i(0),
                             div((a_mem - u_mem) * hundred, a_mem))
            least = (l_cpu + l_mem) // i(2)
            q_cpu = np.minimum(div(u_cpu * scale, a_cpu), scale)
            q_mem = np.minimum(div(u_mem * scale, a_mem), scale)
            balanced = ((hundred * scale - i(50) * np.abs(q_cpu - q_mem))
                        // scale)
        return (least + balanced).astype(np.int64)


class StaleBatch(reference.Reference):
    """Pods placed in groups of 64 against the usage the group started
    with: what scheduling a batch in parallel, without the sequential
    carry, would do (feasibility stays live, so no node overflows)."""

    GROUP = 64

    def resource_scores(self, shape, rows):
        # (a cluster event makes the rows anew: the group starts again)
        if (len(self.placed) % self.GROUP == 0 or not hasattr(self, "_frozen")
                or len(self._frozen[0]) != self.n):
            self._frozen = (self.nz_cpu.copy(), self.nz_mem.copy())
        live = self.nz_cpu, self.nz_mem
        self.nz_cpu, self.nz_mem = self._frozen
        try:
            return super().resource_scores(shape, rows)
        finally:
            self.nz_cpu, self.nz_mem = live


class LastMaximum(reference.Reference):
    """Ties broken the other way: the last maximum in walk order."""

    def scores(self, shape, rows):
        s = super().scores(shape, rows)
        return s * len(s) + np.arange(len(s))


class NodeAddIgnored(reference.Reference):
    """A node created in the middle of the run never joins the cluster."""

    def add_node(self, node):
        pass


class NodeDeleteIgnored(reference.Reference):
    """A removed node stays: its pods still count and its row still takes
    pods."""

    def remove_node(self, name):
        pass


class TreeOrderStale(reference.Reference):
    """A node created in the middle of the run is put at the end of the flat
    list, not into its zone's turn of the node tree."""

    _late = frozenset()           # the nodes that joined after the first

    def add_node(self, node):
        self._late = self._late | {node["name"]}
        super().add_node(node)

    def _walk_order(self):
        order = super()._walk_order()
        return ([n for n in order if n["name"] not in self._late]
                + [n for n in order if n["name"] in self._late])


# A control has to differ on every seed. The float32 reading is kept beside
# them as found (see the module's docstring): an identical result, which a
# guarantee on placements cannot refuse.
CONTROLS = {"int32": Int32Scores, "stale_batch": StaleBatch,
            "last_maximum": LastMaximum}
# read on a log with cluster events (`event_log`): the plain log has none
EVENT_CONTROLS = {"node_add_ignored": NodeAddIgnored,
                  "node_delete_ignored": NodeDeleteIgnored,
                  "tree_order_stale": TreeOrderStale}
READINGS = {"float32": Float32Scores}


def _swapped(key: str, broken_state: type) -> type:
    """The reference with feature `key`'s state replaced by `broken_state`."""

    class Swapped(reference.Reference):
        def feature_state(self, k, module):
            if k == key:
                return broken_state(self)
            return super().feature_state(k, module)

    return Swapped


def every_template(cfg: dict) -> list:
    """Every template of the configuration, wherever it stands (init and
    measured pods, a churn op's objects, preemptors, node groups)."""
    found = []

    def walk(value):
        if isinstance(value, dict):
            if isinstance(value.get("template"), dict):
                found.append(value["template"])
            for v in value.values():
                walk(v)
        elif isinstance(value, list):
            for v in value:
                walk(v)

    walk(cfg)
    return found


def may_pend_templates(cfg: dict) -> list:
    """The templates of the pods that may find no node: the configuration's
    `preemptors`, or the pod of its `churn` op."""
    if "preemptors" in cfg:
        return [g["template"] for g in objects.groups(cfg, "preemptors")]
    pod = cfg.get("churn", {}).get("pod", {})
    return [pod["template"]] if "template" in pod else []


def feature_controls(cfg: dict) -> dict:
    """As `<key>.<name>`, the controls of every pod feature that any of the
    configuration's templates uses."""
    out = {}
    keys = ({k for template in every_template(cfg) for k in template}
            - reference.CORE_POD_KEYS - reference.NODE_KEYS)
    for key in sorted(keys):
        module = features.load("reference", key)
        for name, broken in getattr(module, "CONTROLS", {}).items():
            out[f"{key}.{name}"] = _swapped(key, broken)
    return out


def differing(cfg: dict, seed: int, control: type) -> tuple:
    """(pods compared, placements on which the control differs): the init
    pods and one wave, and a third into the wave one pod of each template
    that may pend."""
    nodes = objects.cluster(cfg, seed)
    sound, other = reference.Reference(nodes), control(nodes)
    differ = total = 0
    died = False        # a control that cannot go on differs from there on

    def place(name, tpl, may_pend=False):
        nonlocal differ, total, died
        want = sound.schedule(name, tpl, may_pend)
        if not died:
            try:
                differ += other.schedule(name, tpl, may_pend) != want
            except (reference.Unschedulable, reference.Unmodelled):
                died = True
        differ += died
        total += 1

    for g, group in enumerate(objects.groups(cfg, "initPods")):
        for i in range(int(group["count"])):
            place(f"initPods-{g}-{i}" if g else f"initPods-{i}",
                  group["template"])
    per = int(cfg["measurePods"]["count"])
    for i in range(per):
        if i == per // 3:
            for k, tpl in enumerate(may_pend_templates(cfg)):
                place(f"mayPend-{k}", tpl, may_pend=True)
        place(f"measurePods-{i}", cfg["measurePods"]["template"])
    return total, differ


PENDING = "cannotFit"      # the template group of `event_log`'s large pod


def event_log(cfg: dict, seed: int) -> dict:
    """A run with cluster events, as a driver would hand it to `run.py`
    (`nodes`, `templates`, `log`, `may_pend`, `placements`), made with the
    reference itself: the init pods, a third of the measured pods, the fullest node of
    the first zone removed, another third, a node of the first group's
    template added to that zone, the rest, one pod that asks for more cpu
    than any node has, and the first third deleted and created again."""
    nodes = objects.cluster(cfg, seed)
    group = objects.node_groups(cfg)[0]
    largest = max(reference.milli_cpu(g["template"]["cpu"])
                  for g in objects.node_groups(cfg))
    templates = {"initPods": cfg["initPods"]["template"],
                 "measurePods": cfg["measurePods"]["template"],
                 PENDING: {"cpu": f"{largest + 1000}m", "memory": "1Gi"}}
    ref = reference.Reference(nodes)
    log, placements = [], {}

    def create(names, which):
        for name in names:
            log.append(("create", name, which))
            placements[name] = ref.schedule(name, templates[which],
                                            may_pend=which == PENDING)

    per = int(cfg["measurePods"]["count"])
    third = [range(0, per // 3), range(per // 3, 2 * per // 3),
             range(2 * per // 3, per)]
    create([f"init-{i}" for i in range(int(cfg["initPods"]["count"]))],
           "initPods")
    create([f"m-{i}" for i in third[0]], "measurePods")
    # both events in the zone whose turn comes first: the node that joins it
    # then stands ahead of the other zones' last nodes, not at the list's end
    first = np.flatnonzero(ref.zone_of == 0)
    fullest = ref.names[int(first[np.argmax(ref.n_pods[first])])]
    log.append(("node_delete", fullest, None))
    ref.remove_node(fullest)
    create([f"m-{i}" for i in third[1]], "measurePods")
    zones = int(group["template"]["zones"])
    index = next(i for i in range(len(nodes), len(nodes) + zones)
                 if f"zone-{i % zones}" == ref.zones[0])
    added = reference.node_description("node-added-0", index,
                                       group["template"])
    log.append(("node_add", added["name"], added))
    ref.add_node(added)
    create([f"m-{i}" for i in third[2]], "measurePods")
    create(["large-0"], PENDING)
    for i in third[0]:
        log.append(("delete", f"m-{i}", None))
        ref.delete(f"m-{i}")
    create([f"again-{i}" for i in third[0]], "measurePods")
    return {"nodes": nodes, "templates": templates, "log": log,
            "may_pend": [PENDING], "placements": placements}


def _held_to(run: dict, sound, control: type) -> tuple:
    """(pods compared, what differs between `sound` and the control's replay
    of `run`'s log: placements, evictions, nominations). A control that
    cannot finish the log (no feasible node for a pod, a pending pod that
    fits, a node it never had, a retry of a pod it has bound) has failed
    from there on: every pod it did not reach counts as differing."""
    broken, other = control(run["nodes"]), reference.Expected()
    try:
        reference.replay(broken, run["templates"], run["log"],
                         run["may_pend"], other)
    except (reference.Unschedulable, reference.Unmodelled, KeyError,
            ValueError):
        other.evictions = dict(broken.evicted)
        other.nominations = dict(broken.nominations)
    cmp_ = reference.compare(sound, other, other.evictions,
                             other.nominations)
    return len(sound), (
        sum(1 for p, node in sound.items() if other.get(p, p) != node)
        + cmp_["evictions_differing"] + cmp_["nominations_differing"])


def differing_on_events(cfg: dict, seed: int, control: type) -> tuple:
    """`_held_to` over `event_log`."""
    run = event_log(cfg, seed)
    return _held_to(run, run["placements"], control)


ROUNDS = 24                # the preemptors of `preemption_log`


def preemption_log(cfg: dict, seed: int) -> dict:
    """A run that preempts, as a driver that deletes victims inside the
    cycle would hand it to `run.py`, made with the reference itself: the
    init pods (every group in turn), then the measured pods in `ROUNDS`
    parts; before each part one pod of the templates that may pend, in
    turn, and the `delete` of each victim the reference evicts for it;
    after the part its `retry`."""
    nodes = objects.cluster(cfg, seed)
    templates = {"measurePods": cfg["measurePods"]["template"]}
    ref = reference.Reference(nodes)
    log = []

    def create(name, which, may_pend=False):
        log.append(("create", name, which))
        ref.schedule(name, templates[which], may_pend)

    for g, group in enumerate(objects.groups(cfg, "initPods")):
        templates[f"initPods-{g}"] = group["template"]
        for i in range(int(group["count"])):
            create(f"init-{g}-{i}", f"initPods-{g}")
    kinds = may_pend_templates(cfg)
    for k, template in enumerate(kinds):
        templates[f"preemptor-{k}"] = template
    per = int(cfg["measurePods"]["count"])
    for r in range(ROUNDS):
        which = f"preemptor-{r % len(kinds)}"
        evicted = set(ref.evicted)
        create(f"high-{r}", which, may_pend=True)
        for victim in ref.evicted:
            if victim not in evicted:
                log.append(("delete", victim, None))
                ref.delete(victim)
        for i in range(r * per // ROUNDS, (r + 1) * per // ROUNDS):
            create(f"m-{i}", "measurePods")
        if f"high-{r}" in ref.pending:
            log.append(("retry", f"high-{r}", None))
            ref.retry(f"high-{r}")
    return {"nodes": nodes, "templates": templates, "log": log,
            "may_pend": [f"preemptor-{k}" for k in range(len(kinds))]}


def differing_on_preemption(cfg: dict, seed: int, control: type) -> tuple:
    """`_held_to` over `preemption_log`."""
    run = preemption_log(cfg, seed)
    sound = reference.replay(reference.Reference(run["nodes"]),
                             run["templates"], run["log"], run["may_pend"])
    return _held_to(run, sound, control)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--events", action="store_true",
                    help="over the log with cluster events, the event "
                         "controls beside the others")
    ap.add_argument("--preemption", action="store_true",
                    help="over a log that preempts, held to placements, "
                         "evictions and nominations")
    ap.add_argument("--bench-dir", default=HERE,
                    help="the directory whose configs/ holds the "
                         "configuration")
    args = ap.parse_args(argv)
    cfg = objects.load_config(
        os.path.join(args.bench_dir, "configs", args.config + ".json"),
        args.rehearse)
    controls = {**CONTROLS, **feature_controls(cfg)}
    count = differing
    if args.events:
        controls, count = {**controls, **EVENT_CONTROLS}, differing_on_events
    elif args.preemption:
        count = differing_on_preemption
    held = set(controls)          # the controls that differed on every seed
    for seed in args.seeds:
        for name, control in {**controls, **READINGS}.items():
            total, differ = count(cfg, seed, control)
            if not differ:
                held.discard(name)
            print(f"{'control' if name in controls else 'reading'} {name} "
                  f"config {args.config} seed {seed}: {differ} of {total} "
                  f"placements{', evictions and nominations' * args.preemption} "
                  f"differ from the reference (limit of the comparison: 0)", flush=True)
    for name in sorted(set(controls) - held):
        print(f"control {name} placed every pod of some seed where the "
              f"reference does: on {args.config} it is a reading, and "
              f"`correct` does not guard what it breaks", flush=True)
    return 0 if held == set(controls) else 1


if __name__ == "__main__":
    sys.exit(main())
