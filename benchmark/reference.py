"""The plain reference: sequential scheduling in numpy, one pod at a time.

Independent of the package under test: it imports nothing of
``kubernetes_tpu`` and takes nothing the program has made. Its inputs are the
plain node and pod descriptions of a configuration file (``configs/*.json``)
in the order the run created them, and the run's log of what happened to
them afterwards (``replay``); its output is the node each pod must land
on when pods are scheduled one after the other in creation order with
deterministic ties, which is the guarantee the configurations state.

Semantics, per pod (kube-scheduler ``schedule_one.go``, default plugin set):

- node order: zone-interleaved round robin (``node_tree.go list()``): zones in
  order of first appearance, nodes of a zone in creation order;
- the adaptive sample: walk the order from the rotating start index and stop at
  ``num_feasible_nodes_to_find`` feasible nodes (50 - n/125 percent, at least
  5 %, at least 100 nodes); the start index advances by the nodes walked;
- feasibility: NodeResourcesFit (cpu, memory, pod count), and every active
  pod feature's own filter;
- score: NodeResourcesFit LeastAllocated + NodeResourcesBalancedAllocation,
  weight 1 each, over non-zero requests (100m / 200Mi defaults), in the
  integer forms the configuration states, plus what an active pod feature
  adds. TaintToleration (3 x 100), NodeAffinity, InterPodAffinity,
  ImageLocality and PodTopologySpread's soft score are the same for every
  node for the core's pods and cannot move the maximum, so a pod or node that
  would make them vary is refused unless a feature file models it;
- the first maximum in walk order wins.

Cluster events (``backend/cache/node_tree.go``, ``cache.go``,
``schedule_one.go``), each applied where the log has it:

- ``add_node``: the node joins the end of its zone's list, a zone first met
  the end of the zones; ``remove_node``: the node leaves its zone's list, and
  a zone left empty leaves the zones. The walk order is the zone-interleaved
  list made anew from that tree, the adaptive sample follows the new count,
  and the rotating start index is kept and taken modulo the new count;
- pods on a removed node stay bound where they were (scheduler_perf runs no
  kubelet and no pod garbage collector) and leave every count: the cache
  drops the node from the tree and the snapshot list, so its pods leave fit,
  spread and term tables alike, and a later delete of one accounts nothing.
  Departure: a node created again under the name of a removed node that
  still holds such pods would get them back in the source's cache
  (``cache.AddNode`` reuses the NodeInfo); that is refused as ``Unmodelled``;
- a pod that must stay pending: for a pod of a template group that the run
  names as one that may pend, no feasible node is an answer (``None``) and
  not ``Unschedulable``. Its failed cycle walks every node, so the start
  index stays where it was, and PostFilter runs (below).

PostFilter and what follows it (``runtime/framework.go``
``RunFilterPluginsWithNominatedPods``, ``schedule_one.go``
``evaluateNominatedNode``; the plugin itself, DefaultPreemption, is the pod
feature ``reference_features/priority.py``, which has the rules of
``preemption.go`` and ``default_preemption.go``):

- a failed cycle hands the pod to every feature state that has a
  ``post_filter``. One that finds victims and a node answers through
  ``nominate``: the core records ``evicted[victim] = preemptor`` and
  ``nominated[preemptor] = node``, holds the preemptor pending, and takes the
  victims out of NO count: they leave where the log has their ``delete``,
  which is where the run's cache saw it (at once where the program deletes in
  the cycle, later where a thread does). A victim that the log never deletes
  is counted (``compare``);
- from the nomination until the preemptor lands or is deleted its requests
  count on the nominated node in the FILTER of every pod that a feature state
  says it ``holds_room`` against (priority: a pod of equal or lower priority),
  itself excepted, and in no score: the filter runs with those pods added to
  the node, and, where that passes, again without them (both passes matter
  to a feature such as pod affinity; for the resource fit the first implies
  the second). A nominated node that leaves the cluster takes the nomination
  with it (departure: the source keeps the name on the pod, finds no such
  node and goes on to the ordinary cycle, which is what follows here too);
- ``retry``, where the log has it, is the scheduler attempting a pending pod
  again: its nominated node is tried first and alone, and if it passes the
  pod lands there with no score and the start index where it was
  (``evaluateNominatedNode``; ``findNodesThatFitPod`` returns before it
  advances the index); otherwise the ordinary cycle, and where that finds
  no node PostFilter again. A ``retry`` of a pod that is not pending is an
  error;
- the order in which a requeued pod meets the pods created meanwhile is the
  log's to say, and only the log's: the reference never retries a pod by
  itself. So that a program cannot starve a pod unseen, after every event
  that could admit a pending pod (a node added or removed, a pod deleted, a
  pod placed while a pod feature has a state) every pending pod for which
  the log has no further ``retry`` and which holds no nomination is checked
  again, and one that has become feasible is refused as ``Unmodelled``: the
  log lacks its ``retry``. A pod that holds a nomination is exempt from its
  eviction to its retry; if it is deleted, or the log ends, while its
  nominated node would take it and no victim is still leaving, that is
  refused too.

This file is the core: it knows the pod template's ``cpu``, ``memory`` and
``labels`` and nothing else. Every further key of a pod template is a pod
feature with a file of its own, ``reference_features/<key>.py`` (found by
``features.py``: ``Reference``'s ``bench_dir`` first, which is the run's
``--bench-dir``, beside this file second),
numpy only, which states its own semantics and refusals and supplies:

- ``parse(value, template) -> terms``: the key's value as the feature's own
  terms, raising ``Unmodelled`` on every sub-key or value it does not model;
  kept as ``pod.features[key]`` on the parsed pod (``PodShape``);
- optionally ``State``, one per ``Reference``, made as ``State(ref)`` when the
  first template that carries the key is met and then told of every pod
  already placed. The core calls, for EVERY pod from then on, whether or not
  it carries the key (a filter like anti-affinity is symmetric):
  ``feasible(pod) -> bool[n] or None`` (a mask over all rows, ``None`` = no
  say), ``score(pod, rows) -> int64[len(rows)] or None`` (already normalised
  and weighted as the default plugin set does it; added to the core's sum
  before the first maximum is taken), ``account(row, pod, sign)`` (a pod
  landed on, +1, or left, -1, that row). ``ref`` offers ``n``, ``names``,
  ``zones``, ``zone_of``, ``n_zones`` and ``placed`` (pod name -> (row, pod));
  when a node is added or removed the rows change, so every ``State`` is made
  anew over the new rows and told again of every pod on a live node;
- optionally on ``State``: ``post_filter(name, pod)`` (the pod found no node:
  the state may call ``ref.nominate``), ``holds_room(nominated, pod) -> bool``
  (whether a nominated pod's requests count in ``pod``'s filter) and
  ``filters_nothing = True`` (its ``feasible`` never has a say, so one row can
  be checked without the others). ``ref`` offers them ``nominated``,
  ``terminating``, ``born``, ``feasible_on`` and ``nominate``;
- optionally ``CONTROLS``: name -> a ``State`` with one guarantee broken,
  which ``control.py`` puts in the feature's place and which has to come out
  as not correct.

A pod key without such a file, and any node key outside the list, raises
``Unmodelled``: the reference never passes what it does not understand.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np

import features

DEFAULT_MILLI_CPU = 100                 # GetNonzeroRequests
DEFAULT_MEMORY = 200 * 1024 * 1024
FRACTION_SCALE = 1_000_000              # BalancedAllocation's integer fractions
MAX_NODE_SCORE = 100
NODE_KEYS = {"cpu", "memory", "pods", "zones"}
CORE_POD_KEYS = {"cpu", "memory", "labels"}

_SUFFIX = {"Ki": 1 << 10, "Mi": 1 << 20, "Gi": 1 << 30, "Ti": 1 << 40,
           "k": 10 ** 3, "M": 10 ** 6, "G": 10 ** 9, "T": 10 ** 12}


class Unmodelled(ValueError):
    """The input uses a feature this reference does not model."""


class Unschedulable(RuntimeError):
    """No feasible node: the traffic is chosen so that this never happens."""


def milli_cpu(q) -> int:
    s = str(q)
    if s.endswith("m"):
        return int(s[:-1])
    return int(round(float(s) * 1000))


def quantity(q) -> int:
    s = str(q)
    for suf in sorted(_SUFFIX, key=len, reverse=True):
        if s.endswith(suf):
            return int(float(s[:-len(suf)]) * _SUFFIX[suf])
    return int(float(s))


def num_feasible_nodes_to_find(num_nodes: int) -> int:
    if num_nodes < 100:
        return num_nodes
    pct = max(50 - num_nodes // 125, 5)
    return max(num_nodes * pct // 100, 100)


def node_description(name: str, index: int, template: dict) -> dict:
    """One node of ``template`` as plain data, in zone ``zone-<index % zones>``
    (what a driver that adds a node in the middle of a run describes it
    with, to the program through ``objects.make_node`` and in its log)."""
    unknown = set(template) - NODE_KEYS
    if unknown:
        # taints, labels and every other node key wait for the PR that
        # needs them: the fit filter and the two resource scores read
        # nothing else of a node
        raise Unmodelled(f"node template keys {sorted(unknown)}")
    zones = int(template["zones"])
    if zones < 1:
        raise Unmodelled("nodes without a zone label")
    return {"name": name, "zone": f"zone-{index % zones}",
            "cpu": milli_cpu(template["cpu"]),
            "memory": quantity(template["memory"]),
            "pods": int(template["pods"])}


def group_descriptions(groups: Sequence[dict],
                       order: Sequence[int]) -> List[dict]:
    """The cluster as plain data, listed in the order the run creates it.
    ``groups`` are a configuration's node groups, each ``count`` nodes of one
    ``template``. Positions run across the groups in the order given: the
    node at position ``k`` is in zone ``zone-<k % zones>`` of its own group's
    template and is named ``node-<i>``, ``i`` counting the unnamed nodes
    before it, so one group reads as it always did. A group of one may give
    its node a ``name`` of its own."""
    described = []
    unnamed = 0
    for g in groups:
        unknown = set(g) - {"count", "template", "name"}
        if unknown:
            raise Unmodelled(f"node group keys {sorted(unknown)}")
        count = int(g["count"])
        if "name" in g and count != 1:
            raise ValueError(f"node group {g['name']!r}: a name is for a "
                             f"group of one, not of {count}")
        for _ in range(count):
            name = g.get("name")
            if name is None:
                name, unnamed = f"node-{unnamed}", unnamed + 1
            described.append(
                node_description(name, len(described), g["template"]))
    if sorted(order) != list(range(len(described))):
        raise ValueError("node order is not a permutation of the nodes")
    return [described[i] for i in order]


def node_descriptions(template: dict, count: int,
                      order: Sequence[int]) -> List[dict]:
    """One group: node ``i`` is ``node-<i>`` in zone ``zone-<i % zones>``."""
    return group_descriptions([{"count": count, "template": template}], order)


class PodShape:
    """One pod template, parsed once: the core's keys, and under ``features``
    each further key's terms as its feature file parsed them."""

    def __init__(self, template: dict):
        self.cpu = milli_cpu(template.get("cpu", 0))
        self.memory = quantity(template.get("memory", 0))
        self.labels = dict(template.get("labels", {}))
        self.nz_cpu = self.cpu or DEFAULT_MILLI_CPU
        self.nz_memory = self.memory or DEFAULT_MEMORY
        self.features: Dict[str, object] = {}


DESCRIPTION_KEYS = {"name", "zone", "cpu", "memory", "pods"}


class Reference:
    """Sequential scheduler over plain arrays, nodes in node-tree order."""

    def __init__(self, nodes: Iterable[dict],
                 bench_dir: Optional[str] = None):
        # where pod features are looked for ahead of this file's directory
        self.bench_dir = bench_dir
        # the node tree: zone -> its nodes in creation order; the dict's own
        # order is the zones' (first appearance, a zone left empty removed)
        self._tree: Dict[str, List[dict]] = {}
        self._zone_of_node: Dict[str, str] = {}
        self.names: List[str] = []
        self.start = 0
        self.placed: Dict[str, tuple] = {}      # pod name -> (row, pod)
        self.pending: Dict[str, PodShape] = {}  # pods that found no node
        self.born: Dict[str, int] = {}          # pod -> its create's ordinal
        self._created = 0
        # PostFilter's record (module docstring): the room held, pod -> (row,
        # pod); every eviction and the last nomination of the run; the
        # victims whose delete the log has not had yet; the retries the log
        # still has for a pod (`replay` counts them); the PostFilters that
        # got as far as looking for candidates (a feature's offset rule)
        self.nominated: Dict[str, tuple] = {}
        self.evicted: Dict[str, str] = {}       # victim -> preemptor
        self.nominations: Dict[str, str] = {}   # preemptor -> node
        self.terminating: Dict[str, str] = {}   # victim -> preemptor
        self.retries_left: Dict[str, int] = {}
        self.candidate_searches = 0
        self._held: Dict[tuple, Optional[tuple]] = {}
        self._gone: Dict[str, str] = {}         # pod -> the removed node it is on
        self._modules: Dict[str, object] = {}   # feature key -> its module
        self._states: Dict[str, object] = {}    # feature key -> its State
        self._shapes: Dict[int, PodShape] = {}
        for n in nodes:
            self._join(n)
        self._rebuild()

    # -- the cluster -------------------------------------------------------

    def _join(self, node: dict) -> None:
        unknown = set(node) - DESCRIPTION_KEYS
        if unknown:
            raise Unmodelled(f"node description keys {sorted(unknown)}")
        if node["name"] in self._zone_of_node:
            raise ValueError(f"duplicate node name {node['name']}")
        if node["name"] in self._gone.values():
            raise Unmodelled(
                f"node {node['name']} created again while pods bound to the "
                f"removed node of that name remain")
        if node["cpu"] <= 0 or node["memory"] <= 0:
            raise Unmodelled("a node without cpu or memory")
        self._tree.setdefault(node["zone"], []).append(node)
        self._zone_of_node[node["name"]] = node["zone"]

    def _walk_order(self) -> List[dict]:
        """The zone-interleaved list (``node_tree.go list()``): zones in
        their order, one node of each in turn, nodes of a zone in the order
        they joined it."""
        ordered: List[dict] = []
        total = sum(len(v) for v in self._tree.values())
        depth = 0
        while len(ordered) < total:
            for nodes in self._tree.values():
                if depth < len(nodes):
                    ordered.append(nodes[depth])
            depth += 1
        return ordered

    def _rebuild(self) -> None:
        """The rows anew from the node tree: the walk order, the arrays over
        it, the sample's size, and every count told again of the pods on the
        nodes that are there."""
        zones = list(self._tree)
        ordered = self._walk_order()
        was = self.names
        self.names = [n["name"] for n in ordered]
        self.n = len(ordered)
        self.zones = zones
        index = {z: i for i, z in enumerate(zones)}
        self.zone_of = np.array([index[n["zone"]] for n in ordered], np.int64)
        self.n_zones = len(zones)
        self.alloc_cpu = np.array([n["cpu"] for n in ordered], np.int64)
        self.alloc_mem = np.array([n["memory"] for n in ordered], np.int64)
        self.alloc_pods = np.array([n["pods"] for n in ordered], np.int64)
        self.to_find = num_feasible_nodes_to_find(self.n)
        # a pod keeps its node: its row is wherever that node now is, and a
        # pod whose node has left stays bound there and leaves every count
        row_of = {name: i for i, name in enumerate(self.names)}
        here = {}
        for pod, (row, shape) in self.placed.items():
            if was[row] in row_of:
                here[pod] = (row_of[was[row]], shape)
            else:
                self._gone[pod] = was[row]
        self.placed = here
        # and so does the room held for a nominated pod, while its node stays
        self.nominated = {pod: (row_of[was[row]], shape)
                          for pod, (row, shape) in self.nominated.items()
                          if was[row] in row_of}
        self._held = {}
        rows = np.array([row for row, _ in here.values()], np.int64)
        shapes = [shape for _, shape in here.values()]
        for held, field in (("req_cpu", "cpu"), ("req_mem", "memory"),
                            ("nz_cpu", "nz_cpu"), ("nz_mem", "nz_memory")):
            summed = np.zeros(self.n, np.int64)
            np.add.at(summed, rows,
                      np.array([getattr(s, field) for s in shapes], np.int64))
            setattr(self, held, summed)
        self.n_pods = np.bincount(rows, minlength=self.n).astype(np.int64)
        self._states = {}
        for key, module in self._modules.items():
            state = self._states[key] = self.feature_state(key, module)
            for row, shape in here.values():
                state.account(row, shape, +1)

    def add_node(self, node: dict) -> None:
        """A node (one plain description) joins the cluster."""
        self._join(dict(node))
        self._rebuild()
        self._nothing_pending_fits(f"node {node['name']} was added")

    def remove_node(self, name: str) -> None:
        """A node leaves; the pods on it stay bound and leave every count."""
        if name not in self._zone_of_node:
            raise KeyError(f"no node {name} to remove")
        zone = self._zone_of_node.pop(name)
        self._tree[zone] = [n for n in self._tree[zone] if n["name"] != name]
        if not self._tree[zone]:
            del self._tree[zone]
        self._rebuild()
        self._nothing_pending_fits(f"node {name} was removed")

    def _nothing_pending_fits(self, after: str) -> None:
        seen = set()
        for pod, shape in self.pending.items():
            if pod in self.nominated or self.retries_left.get(pod):
                continue        # between its eviction and its retry
            if id(shape) in seen:
                continue
            seen.add(id(shape))
            if self.n and self.feasible(shape, pod).any():
                raise Unmodelled(
                    f"pending pod {pod} has a feasible node after {after}, "
                    f"and the log has no `retry` of it from here on: a "
                    f"requeued pod is retried where the log says, and "
                    f"nowhere else")

    def _left_waiting(self, name: str, when: str) -> None:
        """A pod whose room is held, whose victims have all left and whose
        nominated node would take it, and which the log does not retry."""
        held = self.nominated.get(name)
        if held is None or self.retries_left.get(name):
            return
        row, shape = held
        if any(self.placed.get(v, (None,))[0] == row
               for v in self.terminating):
            return
        if self.feasible_on(shape, row, name):
            raise Unmodelled(
                f"pod {name} {when} while node {self.names[row]}, to which "
                f"it is nominated, would take it: the log lacks its `retry`")

    # -- bookkeeping -------------------------------------------------------

    def feature_state(self, key: str, module):
        """The state of feature ``key`` for this cluster (a control puts
        another in its place)."""
        return module.State(self)

    def _shape(self, template: dict) -> PodShape:
        s = self._shapes.get(id(template))
        if s is not None:
            return s
        s = PodShape(template)
        # keep the template alive so its id stays its own
        s.template = template
        keys = [k for k in template if k not in CORE_POD_KEYS]
        modules = {k: features.load("reference", k, self.bench_dir)
                   for k in keys}
        unknown = sorted(k for k, m in modules.items() if m is None)
        if unknown:
            raise Unmodelled(f"pod template keys {unknown}")
        for key, module in modules.items():
            s.features[key] = module.parse(template[key], template)
            if key not in self._states and hasattr(module, "State"):
                self._modules[key] = module
                state = self._states[key] = self.feature_state(key, module)
                for row, pod in self.placed.values():
                    state.account(row, pod, +1)
        self._shapes[id(template)] = s
        return s

    def _account(self, row: int, shape: PodShape, sign: int) -> None:
        self.req_cpu[row] += sign * shape.cpu
        self.req_mem[row] += sign * shape.memory
        self.nz_cpu[row] += sign * shape.nz_cpu
        self.nz_mem[row] += sign * shape.nz_memory
        self.n_pods[row] += sign
        for state in self._states.values():
            state.account(row, shape, sign)

    # -- one scheduling cycle ----------------------------------------------

    def _held_against(self, shape: PodShape, name: Optional[str]):
        """What the nominated pods hold against ``shape`` (``name``'s own
        nomination excepted): their cpu, memory and count a row, and the
        pods themselves by row; None where nothing is held against it
        (``addGENominatedPods``). Kept until a nomination changes."""
        key = (id(shape), name if name in self.nominated else None)
        if key not in self._held:
            by_row: Dict[int, list] = {}
            for other, (row, pod) in self.nominated.items():
                if other != name and any(
                        state.holds_room(pod, shape)
                        for state in self._states.values()
                        if hasattr(state, "holds_room")):
                    by_row.setdefault(row, []).append(pod)
            held = None
            if by_row:
                cpu, mem, pods = (np.zeros(self.n, np.int64)
                                  for _ in range(3))
                for row, there in by_row.items():
                    cpu[row] = sum(p.cpu for p in there)
                    mem[row] = sum(p.memory for p in there)
                    pods[row] = len(there)
                held = (cpu, mem, pods, by_row)
            self._held[key] = held
        return self._held[key]

    def _say(self, shape: PodShape, ok: np.ndarray) -> np.ndarray:
        for state in self._states.values():
            mask = state.feasible(shape)
            if mask is not None:
                ok &= mask
        return ok

    def feasible(self, shape: PodShape,
                 name: Optional[str] = None) -> np.ndarray:
        """The rows that pass the filters for a pod of ``shape`` (``name``:
        the pod itself where it may hold a nomination, which does not count
        against it)."""
        ok = self.n_pods + 1 <= self.alloc_pods
        if shape.cpu > 0:
            ok &= shape.cpu <= self.alloc_cpu - self.req_cpu
        if shape.memory > 0:
            ok &= shape.memory <= self.alloc_mem - self.req_mem
        ok = self._say(shape, ok)
        held = self._held_against(shape, name) if self.nominated else None
        if held is not None:
            # RunFilterPluginsWithNominatedPods: with the nominated pods
            # added to their node, and (above) without them
            cpu, mem, pods, by_row = held
            ok &= self.n_pods + pods + 1 <= self.alloc_pods
            if shape.cpu > 0:
                ok &= shape.cpu <= self.alloc_cpu - self.req_cpu - cpu
            if shape.memory > 0:
                ok &= shape.memory <= self.alloc_mem - self.req_mem - mem
            if not self._row_local():
                for row, there in by_row.items():
                    if ok[row]:
                        for pod in there:
                            for state in self._states.values():
                                state.account(row, pod, +1)
                        ok[row] = self._say(
                            shape, np.ones(self.n, bool))[row]
                        for pod in there:
                            for state in self._states.values():
                                state.account(row, pod, -1)
        return ok

    def _row_local(self) -> bool:
        """No feature's filter has a say: a row's answer needs no other."""
        return all(getattr(state, "filters_nothing", False)
                   for state in self._states.values())

    def feasible_on(self, shape: PodShape, row: int,
                    name: Optional[str] = None) -> bool:
        """``feasible(shape, name)[row]``."""
        if not self._row_local():
            return bool(self.feasible(shape, name)[row])
        held = self._held_against(shape, name) if self.nominated else None
        cpu, mem, pods = ((held[0][row], held[1][row], held[2][row])
                          if held is not None else (0, 0, 0))
        return bool(
            self.n_pods[row] + pods + 1 <= self.alloc_pods[row]
            and (shape.cpu <= 0 or shape.cpu
                 <= self.alloc_cpu[row] - self.req_cpu[row] - cpu)
            and (shape.memory <= 0 or shape.memory
                 <= self.alloc_mem[row] - self.req_mem[row] - mem))

    def resource_scores(self, shape: PodShape, rows: np.ndarray) -> np.ndarray:
        """LeastAllocated + BalancedAllocation for the candidate rows; the
        other default score plugins are constant over nodes (see module
        docstring)."""
        a_cpu, a_mem = self.alloc_cpu[rows], self.alloc_mem[rows]
        u_cpu = self.nz_cpu[rows] + shape.nz_cpu
        u_mem = self.nz_mem[rows] + shape.nz_memory
        l_cpu = np.where(u_cpu > a_cpu, 0,
                         (a_cpu - u_cpu) * MAX_NODE_SCORE // a_cpu)
        l_mem = np.where(u_mem > a_mem, 0,
                         (a_mem - u_mem) * MAX_NODE_SCORE // a_mem)
        least = (l_cpu + l_mem) // 2
        q_cpu = np.minimum(u_cpu * FRACTION_SCALE // a_cpu, FRACTION_SCALE)
        q_mem = np.minimum(u_mem * FRACTION_SCALE // a_mem, FRACTION_SCALE)
        balanced = ((MAX_NODE_SCORE * FRACTION_SCALE
                     - 50 * np.abs(q_cpu - q_mem)) // FRACTION_SCALE)
        return least + balanced

    def scores(self, shape: PodShape, rows: np.ndarray) -> np.ndarray:
        """What the first maximum is taken over: the core's sum plus each
        active feature's contribution."""
        total = self.resource_scores(shape, rows)
        for state in self._states.values():
            more = state.score(shape, rows)
            if more is not None:
                total = total + more
        return total

    def schedule(self, name: str, template: dict,
                 may_pend: bool = False) -> Optional[str]:
        """Place one pod; returns the node's name. A pod that finds no
        feasible node raises ``Unschedulable`` unless it ``may_pend``: then
        it is held as pending, PostFilter runs, and the answer is ``None``."""
        if name in self.placed or name in self.pending or name in self._gone:
            raise ValueError(f"pod {name} scheduled twice")
        self.born[name] = self._created
        self._created += 1
        return self._cycle(name, self._shape(template), may_pend)

    def retry(self, name: str) -> Optional[str]:
        """The scheduler attempts a pending pod again: its nominated node
        first and alone, then the ordinary cycle."""
        shape = self.pending.get(name)
        if shape is None:
            raise ValueError(f"retry of pod {name}, which is not pending")
        if self.retries_left.get(name):
            self.retries_left[name] -= 1
        held = self.nominated.get(name)
        if held is not None and self.feasible_on(shape, held[0], name):
            return self._land(name, shape, held[0])
        return self._cycle(name, shape, may_pend=True)

    def _cycle(self, name: str, shape: PodShape,
               may_pend: bool) -> Optional[str]:
        start = self.start % self.n if self.n else 0
        ok = self.feasible(shape, name)
        # the walk: rows start, start+1, ..., n-1, 0, ..., start-1
        walked = np.concatenate((ok[start:], ok[:start]))
        found = np.cumsum(walked)
        if not self.n or found[-1] == 0:
            if not may_pend:
                raise Unschedulable(f"pod {name}: no feasible node")
            # every node was walked: (start + n) % n, the index stays
            self.pending[name] = shape
            for state in list(self._states.values()):
                if hasattr(state, "post_filter"):
                    state.post_filter(name, shape)
            return None
        if found[-1] >= self.to_find:
            evaluated = int(np.searchsorted(found, self.to_find)) + 1
        else:
            evaluated = self.n
        rows = (np.flatnonzero(walked[:evaluated]) + start) % self.n
        self.start = (start + evaluated) % self.n
        return self._land(name, shape, int(
            rows[0] if len(rows) == 1
            else rows[np.argmax(self.scores(shape, rows))]))

    def _land(self, name: str, shape: PodShape, row: int) -> str:
        self._account(row, shape, +1)
        self.placed[name] = (row, shape)
        self.pending.pop(name, None)
        if self.nominated.pop(name, None) is not None:
            self._held = {}
        if self._states:
            # a pod that landed can admit another only through a feature
            self._nothing_pending_fits(f"pod {name} was placed")
        return self.names[row]

    def nominate(self, name: str, row: int, victims: Iterable[str],
                 cleared: Iterable[str] = ()) -> None:
        """A PostFilter's answer: ``victims`` are to leave row ``row`` for
        pending pod ``name``, whose room is held there from now on; the
        pods in ``cleared`` lose the room held for them
        (``prepareCandidate``). A victim that is leaving already stays its
        first preemptor's."""
        self.nominated[name] = (row, self.pending[name])
        self.nominations[name] = self.names[row]
        for other in cleared:
            self.nominated.pop(other, None)
        for victim in victims:
            self.evicted.setdefault(victim, name)
            self.terminating.setdefault(victim, name)
        self._held = {}

    def delete(self, name: str) -> None:
        """A pod leaves: a bound one frees its node (an expected victim
        among them), a pending one is forgotten with the room held for it,
        one on a removed node accounts nothing."""
        self.born.pop(name, None)
        self.terminating.pop(name, None)
        if name in self.pending:
            self._left_waiting(name, "is deleted")
            del self.pending[name]
            self.retries_left.pop(name, None)
            if self.nominated.pop(name, None) is not None:
                self._held = {}
                self._nothing_pending_fits(
                    f"pod {name} and the room held for it went")
        elif name in self._gone:
            del self._gone[name]
        else:
            row, shape = self.placed.pop(name)
            self._account(row, shape, -1)
            self._nothing_pending_fits(f"pod {name} was deleted")

    def close(self) -> None:
        """The log's end: no pod is left waiting for a retry that would
        have bound it."""
        for name in list(self.nominated):
            self._left_waiting(name, "is pending at the log's end")

    def over_allocatable(self) -> List[str]:
        bad = ((self.req_cpu > self.alloc_cpu) | (self.req_mem > self.alloc_mem)
               | (self.n_pods > self.alloc_pods))
        return [self.names[i] for i in np.flatnonzero(bad)]


class Expected(dict):
    """What a log must have given: pod -> node (``None``: pending to the
    end), and beside it what PostFilter must have done: ``evictions``
    (victim -> preemptor), ``nominations`` (preemptor -> the node it was
    last nominated to) and ``never_deleted`` (victims whose delete the log
    never had)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.evictions: Dict[str, str] = {}
        self.nominations: Dict[str, str] = {}
        self.never_deleted: Sequence[str] = ()


OPERATIONS = ("create", "delete", "retry", "node_add", "node_delete")


def replay(ref: Reference, templates: Dict[str, dict], log: Iterable[tuple],
           may_pend: Iterable[str] = (),
           expected: Optional[Expected] = None) -> Expected:
    """``ref`` over what a run did, in the order it did it: the node each
    pod created must be bound to, ``None`` for one that must stay pending.
    ``log`` entries, by name: ``("create", pod, group)`` (``templates[group]``
    is its template; a group named in ``may_pend`` may find no node),
    ``("delete", pod, None)``, ``("retry", pod, None)`` (the scheduler
    attempted a pending pod again), ``("node_add", name, description)``,
    ``("node_delete", name, None)``. Any other operation is an error.
    ``expected``, where given, is filled as the log goes, so that a caller
    who catches a refusal holds what came before it."""
    may_pend = set(may_pend)
    unknown = may_pend - set(templates)
    if unknown:
        raise ValueError(f"may_pend names no template: {sorted(unknown)}")
    log = log if isinstance(log, (list, tuple)) else list(log)
    for op, name, _ in log:
        if op == "retry":
            ref.retries_left[name] = ref.retries_left.get(name, 0) + 1
    expected = Expected() if expected is None else expected
    for op, name, arg in log:
        if op == "create":
            expected[name] = ref.schedule(name, templates[arg],
                                          may_pend=arg in may_pend)
        elif op == "delete":
            ref.delete(name)
        elif op == "retry":
            expected[name] = ref.retry(name)
        elif op == "node_add":
            if arg["name"] != name:
                raise ValueError(f"node_add {name}: described as "
                                 f"{arg['name']}")
            ref.add_node(arg)
        elif op == "node_delete":
            ref.remove_node(name)
        else:
            raise ValueError(f"log operation {op!r} ({name}) is none of "
                             f"{', '.join(OPERATIONS)}")
    ref.close()
    expected.evictions = dict(ref.evicted)
    expected.nominations = dict(ref.nominations)
    expected.never_deleted = sorted(ref.terminating)
    return expected


def _unlike(want: Dict[str, str], got: Dict[str, str]) -> list:
    return [(k, want.get(k), got.get(k)) for k in sorted(set(want) | set(got))
            if want.get(k) != got.get(k)]


def compare(expected: Dict[str, Optional[str]],
            got: Dict[str, Optional[str]],
            evictions: Optional[Dict[str, str]] = None,
            nominations: Optional[Dict[str, str]] = None) -> dict:
    """Every placement equal, every pod bound once: the exact comparison
    (limit 0 differing placements, 0 unbound, 0 unexpected). A pod expected
    to stay pending (``None``) must be unbound in the run: bound anywhere it
    is a differing placement, and unbound it is not counted as unbound.
    An evicted pod's expected node stays the node it was bound to.

    ``evictions`` (victim -> preemptor) and ``nominations`` (preemptor ->
    the node it was last nominated to) are the run's, absent where it made
    none; they are held to ``expected``'s own (``replay``'s ``Expected``; a
    plain dict expects none), every entry equal on both sides, and a victim
    the log never deleted counts as a differing eviction."""
    differ = [(p, n, got.get(p)) for p, n in expected.items()
              if (got.get(p) or None) != n]
    unbound = [p for p, n in expected.items() if n and not got.get(p)]
    extra = [p for p in got if p not in expected]
    want_evictions = getattr(expected, "evictions", {})
    evicted = _unlike(want_evictions, evictions or {}) + [
        (v, "deleted", "never") for v in getattr(expected, "never_deleted", ())]
    nominated = _unlike(getattr(expected, "nominations", {}),
                        nominations or {})
    return {"compared": len(expected), "differing": len(differ),
            "unbound": len(unbound), "unexpected": len(extra),
            "pending": sum(1 for n in expected.values() if n is None),
            "examples": differ[:3],
            "evictions": len(want_evictions),
            "evictions_differing": len(evicted),
            "nominations_differing": len(nominated),
            "preemption_examples": (evicted + nominated)[:3]}
