"""The plain reference: sequential scheduling in numpy, one pod at a time.

Independent of the package under test: it imports nothing of
``kubernetes_tpu`` and takes nothing the program has made. Its inputs are the
plain node and pod descriptions of a configuration file (``configs/*.json``)
in the order the run created them; its output is the node each pod must land
on when pods are scheduled one after the other in creation order with
deterministic ties, which is the guarantee both configurations state.

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


def node_descriptions(template: dict, count: int,
                      order: Sequence[int]) -> List[dict]:
    """The cluster as plain data: node ``i`` is ``node-<i>`` in zone
    ``zone-<i % zones>``, listed in the order the run creates them."""
    unknown = set(template) - NODE_KEYS
    if unknown:
        raise Unmodelled(f"node template keys {sorted(unknown)}")
    zones = int(template["zones"])
    if zones < 1:
        raise Unmodelled("nodes without a zone label")
    if sorted(order) != list(range(count)):
        raise ValueError("node order is not a permutation of the nodes")
    return [{"name": f"node-{i}", "zone": f"zone-{i % zones}",
             "cpu": milli_cpu(template["cpu"]),
             "memory": quantity(template["memory"]),
             "pods": int(template["pods"])} for i in order]


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


class Reference:
    """Sequential scheduler over plain arrays, nodes in node-tree order."""

    def __init__(self, nodes: Iterable[dict],
                 bench_dir: Optional[str] = None):
        # where pod features are looked for ahead of this file's directory
        self.bench_dir = bench_dir
        by_zone: Dict[str, List[dict]] = {}
        for n in nodes:
            by_zone.setdefault(n["zone"], []).append(n)
        zones = list(by_zone)
        ordered: List[dict] = []
        depth = 0
        while len(ordered) < sum(len(v) for v in by_zone.values()):
            for z in zones:
                if depth < len(by_zone[z]):
                    ordered.append(by_zone[z][depth])
            depth += 1
        self.names = [n["name"] for n in ordered]
        if len(set(self.names)) != len(self.names):
            raise ValueError("duplicate node names")
        self.n = len(ordered)
        self.zones = zones
        self.zone_of = np.array([zones.index(n["zone"]) for n in ordered])
        self.n_zones = len(zones)
        self.alloc_cpu = np.array([n["cpu"] for n in ordered], np.int64)
        self.alloc_mem = np.array([n["memory"] for n in ordered], np.int64)
        self.alloc_pods = np.array([n["pods"] for n in ordered], np.int64)
        if (self.alloc_cpu <= 0).any() or (self.alloc_mem <= 0).any():
            raise Unmodelled("a node without cpu or memory")
        z = np.zeros(self.n, np.int64)
        self.req_cpu, self.req_mem = z.copy(), z.copy()
        self.nz_cpu, self.nz_mem = z.copy(), z.copy()
        self.n_pods = z.copy()
        self.start = 0
        self.to_find = num_feasible_nodes_to_find(self.n)
        self.placed: Dict[str, tuple] = {}      # pod name -> (row, pod)
        self._states: Dict[str, object] = {}    # feature key -> its State
        self._shapes: Dict[int, PodShape] = {}

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

    def feasible(self, shape: PodShape) -> np.ndarray:
        ok = self.n_pods + 1 <= self.alloc_pods
        if shape.cpu > 0:
            ok &= shape.cpu <= self.alloc_cpu - self.req_cpu
        if shape.memory > 0:
            ok &= shape.memory <= self.alloc_mem - self.req_mem
        for state in self._states.values():
            mask = state.feasible(shape)
            if mask is not None:
                ok &= mask
        return ok

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

    def schedule(self, name: str, template: dict) -> str:
        """Place one pod; returns the node's name."""
        if name in self.placed:
            raise ValueError(f"pod {name} scheduled twice")
        shape = self._shape(template)
        start = self.start % self.n
        ok = self.feasible(shape)
        # the walk: rows start, start+1, ..., n-1, 0, ..., start-1
        walked = np.concatenate((ok[start:], ok[:start]))
        found = np.cumsum(walked)
        if found[-1] == 0:
            raise Unschedulable(f"pod {name}: no feasible node")
        if found[-1] >= self.to_find:
            evaluated = int(np.searchsorted(found, self.to_find)) + 1
        else:
            evaluated = self.n
        rows = (np.flatnonzero(walked[:evaluated]) + start) % self.n
        self.start = (start + evaluated) % self.n
        row = int(rows[0] if len(rows) == 1
                  else rows[np.argmax(self.scores(shape, rows))])
        self._account(row, shape, +1)
        self.placed[name] = (row, shape)
        return self.names[row]

    def delete(self, name: str) -> None:
        row, shape = self.placed.pop(name)
        self._account(row, shape, -1)

    def over_allocatable(self) -> List[str]:
        bad = ((self.req_cpu > self.alloc_cpu) | (self.req_mem > self.alloc_mem)
               | (self.n_pods > self.alloc_pods))
        return [self.names[i] for i in np.flatnonzero(bad)]


def compare(expected: Dict[str, str], got: Dict[str, Optional[str]]) -> dict:
    """Every placement equal, every pod bound once: the exact comparison
    (limit 0 differing placements, 0 unbound, 0 unexpected)."""
    differ = [(p, n, got.get(p)) for p, n in expected.items()
              if got.get(p) != n]
    unbound = [p for p in expected if not got.get(p)]
    extra = [p for p in got if p not in expected]
    return {"compared": len(expected), "differing": len(differ),
            "unbound": len(unbound), "unexpected": len(extra),
            "examples": differ[:3]}
