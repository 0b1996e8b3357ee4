"""Required node affinity that pins a pod to named nodes: the DaemonSet
controller's shape (scheduler_perf ``templates/daemonset-pod.yaml``).

Template value, exactly::

    {"requiredDuringSchedulingIgnoredDuringExecution": {"nodeSelectorTerms": [
        {"matchFields": [{"key": "metadata.name", "operator": "In",
                          "values": ["<node>", ...]}]}, ...]}}

one or more terms, each with ONE requirement, a ``matchFields`` on
``metadata.name`` with operator ``In`` and at least one value. Terms are
ORed (``nodeaffinity.go``): a node passes the NodeAffinity filter where any
term names it. ``parse`` gives the set of names; ``State.feasible`` the rows
whose node is named, ``None`` for a pod without the key (the filter has no
say over it). Nothing is scored (no preferred terms are modelled) and nothing
is accounted: a pin reads no pod.

Refused as ``Unmodelled``: ``matchExpressions`` (node labels: the reference
has none), ``preferredDuringSchedulingIgnoredDuringExecution``, a term with
more or fewer than one requirement, any other field or operator, an empty
``values``, any further sub-key.

**The start index after a pinned pod: three rules, and what this file does.**
NodeAffinity's PreFilter answers such a pod with a PreFilterResult that
narrows the cycle to the named nodes, BEFORE the sample. ``reference._cycle``
has no seam for that, so here the pin speaks through the filter mask: a
pinned pod walks every row, finds fewer feasible rows than the sample asks
for, and leaves ``ref.start`` where it was ((start + n) % n). The program's
host path (``core/scheduler.py`` ``find_nodes_that_pass_filters``), to which
its device path is held pod for pod, walks the NARROWED list and takes the
index modulo the narrowed count: after a pod pinned to one node it reads 0.
Upstream's ``findNodesThatFitPod``, as remembered (no checkout of the source
is here), advances ``nextStartNodeIndex`` by the processed nodes modulo
``len(allNodes)``. A configuration whose every pod is pinned (daemonset-15k)
cannot tell the three apart: no pod reads the index. A mixed log would:
pinned pods, then plain pods on nodes that tie, whose first maximum in walk
order is the index's to choose. Until ``reference._cycle`` has a narrowing
seam (a ``benchmark`` PR's: this file may not edit it), such a log is not to
be compared with this feature, and the sample is not cut for a pin to 100 or
more nodes either (the walk passes all rows); both are written down in
``PERF.md`` section 7.
"""

import numpy as np

from reference import Unmodelled

KEY = "nodeAffinity"
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"


def parse(value, template: dict) -> frozenset:
    """The names any term pins the pod to."""
    if not isinstance(value, dict) or set(value) != {REQUIRED}:
        raise Unmodelled(
            f"nodeAffinity keys {sorted(value) if isinstance(value, dict) else value!r}: "
            f"only {REQUIRED} is modelled")
    required = value[REQUIRED]
    if set(required) != {"nodeSelectorTerms"} or not required["nodeSelectorTerms"]:
        raise Unmodelled(f"nodeAffinity {REQUIRED} keys {sorted(required)}")
    names = set()
    for term in required["nodeSelectorTerms"]:
        if set(term) != {"matchFields"} or len(term["matchFields"]) != 1:
            raise Unmodelled(
                f"nodeSelectorTerm {term!r}: one matchFields requirement a "
                f"term is modelled, nothing else (no matchExpressions)")
        req = term["matchFields"][0]
        if (set(req) != {"key", "operator", "values"}
                or req["key"] != "metadata.name" or req["operator"] != "In"
                or not req["values"]
                or not all(isinstance(v, str) and v for v in req["values"])):
            raise Unmodelled(
                f"matchFields requirement {req!r}: only metadata.name In "
                f"[names] is modelled")
        names.update(req["values"])
    return frozenset(names)


class State:
    """The NodeAffinity filter of pinned pods over the cluster's rows."""

    def __init__(self, ref):
        self.ref = ref
        self._masks = {}    # names -> bool[n]; the rows are this state's own

    def feasible(self, pod):
        names = pod.features.get(KEY)
        if names is None:
            return None
        mask = self._masks.get(names)
        if mask is None:
            mask = self._masks[names] = np.array(
                [name in names for name in self.ref.names], bool)
        return mask

    def score(self, pod, rows):
        return None

    def account(self, row, pod, sign) -> None:
        pass


class PinIgnored(State):
    """Control: the pin dropped, as a device path would that planned for a
    pinned template as for a plain one."""

    def feasible(self, pod):
        return None


CONTROLS = {"pin_ignored": PinIgnored}
