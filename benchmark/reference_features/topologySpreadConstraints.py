"""Hard zone spread: PodTopologySpread's filter over the zone label.

Template value: a list of constraints, each with ``maxSkew`` (default 1),
``topologyKey`` (the zone label, also the default), ``whenUnsatisfiable``
(``DoNotSchedule``, also the default) and ``labelSelector`` (a plain map of
labels, all of which must be equal; default: the pod's own labels).

Semantics (kube-scheduler ``podtopologyspread/filtering.go``): for each
constraint, with ``count[z]`` the placed pods that match the selector in zone
``z`` and ``self`` 1 if the incoming pod matches its own selector, a node of
zone ``z`` is refused when ``count[z] + self - min(count) > maxSkew``. Every
node carries the zone label and every zone counts, so the minimum is over all
zones of the cluster. It scores nothing: the hard filter moves no default
score, and a soft constraint (which would) is refused.

Refused as ``Unmodelled``: a constraint key outside the four above
(``minDomains``, ``matchLabelKeys``, the node inclusion policies), a topology
key other than the zone label, ``whenUnsatisfiable`` other than
``DoNotSchedule``.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from reference import Unmodelled

KEY = "topologySpreadConstraints"
ZONE_KEY = "topology.kubernetes.io/zone"
CONSTRAINT_KEYS = {"maxSkew", "topologyKey", "whenUnsatisfiable",
                   "labelSelector"}


def parse(value, template: dict) -> list:
    """[(maxSkew, selector)], the selector as sorted (label, value) pairs."""
    constraints = []
    for c in value:
        unknown = set(c) - CONSTRAINT_KEYS
        if unknown:
            raise Unmodelled(f"spread constraint keys {sorted(unknown)}")
        if c.get("topologyKey", ZONE_KEY) != ZONE_KEY:
            raise Unmodelled(f"spread over {c.get('topologyKey')!r}")
        if c.get("whenUnsatisfiable", "DoNotSchedule") != "DoNotSchedule":
            raise Unmodelled("soft spread constraints change the score")
        selector = dict(c.get("labelSelector", template.get("labels", {})))
        constraints.append(
            (int(c.get("maxSkew", 1)), tuple(sorted(selector.items()))))
    return constraints


def _matches(selector: tuple, labels: dict) -> bool:
    return all(labels.get(k) == v for k, v in selector)


class State:
    """Matching pods per zone, one count vector for each selector met."""

    def __init__(self, ref):
        self.ref = ref
        self._zone_counts: Dict[tuple, np.ndarray] = {}

    def _counts(self, selector: tuple) -> np.ndarray:
        c = self._zone_counts.get(selector)
        if c is None:
            c = np.zeros(self.ref.n_zones, np.int64)
            for row, pod in self.ref.placed.values():
                if _matches(selector, pod.labels):
                    c[self.ref.zone_of[row]] += 1
            self._zone_counts[selector] = c
        return c

    def account(self, row: int, pod, sign: int) -> None:
        for selector, counts in self._zone_counts.items():
            if _matches(selector, pod.labels):
                counts[self.ref.zone_of[row]] += sign

    def feasible(self, pod):
        ok = None
        for max_skew, selector in pod.features.get(KEY, ()):
            counts = self._counts(selector)
            self_match = 1 if _matches(selector, pod.labels) else 0
            mask = (counts[self.ref.zone_of] + self_match - counts.min()
                    <= max_skew)
            ok = mask if ok is None else ok & mask
        return ok

    def score(self, pod, rows):
        return None
