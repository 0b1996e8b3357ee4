"""Required pod anti-affinity over the hostname: InterPodAffinity's filter.

Template value, in the shape of the pod spec:

    {"requiredDuringSchedulingIgnoredDuringExecution": [
        {"labelSelector": {"matchLabels": {"color": "green"}},
         "topologyKey": "kubernetes.io/hostname",
         "namespaces": ["sched-0", "sched-1"]}]}

``namespaces`` is optional; without it a term selects in its owner's own
namespace (the template's ``namespace`` key, default ``default``).

Semantics (kube-scheduler ``interpodaffinity/filtering.go``): a term of pod
``a`` matches pod ``b`` when ``b``'s namespace is among the term's and every
``matchLabels`` pair equals ``b``'s label. With the hostname as topology key a
domain is one node, so a node is refused for the incoming pod when a pod on it
matches one of the incoming pod's terms, **or** when a pod on it carries a
term that the incoming pod matches (the filter is symmetric: a pod already
there refuses the one coming). Every node has a hostname, so no node escapes
by lacking the key. Required anti-affinity moves no default score (the
InterPodAffinity score reads preferred terms and required *affinity* only),
so this feature scores nothing.

Refused as ``Unmodelled``: preferred terms (they change the score), a topology
key other than the hostname (domains wider than a node), ``matchExpressions``,
an empty or missing ``matchLabels`` (select-all and select-none), namespace
selectors, ``matchLabelKeys`` / ``mismatchLabelKeys``, and any other key.
Pod *affinity* is another template key and has no file.

Controls (``control.py`` finds them as ``podAntiAffinity.<name>``):
``filter_dropped`` refuses no node; ``symmetric_half_dropped`` looks only at
the incoming pod's own terms.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from reference import Unmodelled

KEY = "podAntiAffinity"
HOSTNAME_KEY = "kubernetes.io/hostname"
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
TERM_KEYS = {"labelSelector", "topologyKey", "namespaces"}


def parse(value, template: dict) -> list:
    """[(namespaces or None, selector)], the selector as sorted (label,
    value) pairs."""
    unknown = set(value) - {REQUIRED}
    if unknown:
        raise Unmodelled(f"{KEY} keys {sorted(unknown)}")
    terms = []
    for t in value.get(REQUIRED, ()):
        unknown = set(t) - TERM_KEYS
        if unknown:
            raise Unmodelled(f"anti-affinity term keys {sorted(unknown)}")
        if t.get("topologyKey") != HOSTNAME_KEY:
            raise Unmodelled(f"anti-affinity over {t.get('topologyKey')!r}")
        selector = t.get("labelSelector") or {}
        if set(selector) != {"matchLabels"} or not selector["matchLabels"]:
            raise Unmodelled(f"anti-affinity label selector {selector!r}")
        namespaces = t.get("namespaces")
        if namespaces is not None and not namespaces:
            raise Unmodelled("an empty namespaces list")
        terms.append((frozenset(namespaces) if namespaces else None,
                      tuple(sorted(selector["matchLabels"].items()))))
    return terms


def _namespace(pod) -> str:
    return pod.features.get("namespace", "default")


def _selects(owner, target) -> bool:
    """Does one of `owner`'s terms match `target`?"""
    for namespaces, selector in owner.features.get(KEY, ()):
        if (_namespace(target) in (namespaces or (_namespace(owner),))
                and all(target.labels.get(k) == v for k, v in selector)):
            return True
    return False


class State:
    """Pods on each node, one count vector for each pod template met."""

    def __init__(self, ref):
        self.n = ref.n
        self._on: Dict[object, np.ndarray] = {}         # pod shape -> count[n]
        self._refuses: Dict[tuple, bool] = {}

    def refuses(self, there, incoming) -> bool:
        return _selects(incoming, there) or _selects(there, incoming)

    def account(self, row: int, pod, sign: int) -> None:
        on = self._on.get(pod)
        if on is None:
            on = self._on[pod] = np.zeros(self.n, np.int64)
        on[row] += sign

    def feasible(self, pod):
        ok = None
        for there, on in self._on.items():
            refused = self._refuses.get((there, pod))
            if refused is None:
                refused = self._refuses[there, pod] = self.refuses(there, pod)
            if refused:
                ok = (on == 0) if ok is None else ok & (on == 0)
        return ok

    def score(self, pod, rows):
        return None


class FilterDropped(State):
    def refuses(self, there, incoming) -> bool:
        return False


class SymmetricHalfDropped(State):
    def refuses(self, there, incoming) -> bool:
        return _selects(incoming, there)


CONTROLS = {"filter_dropped": FilterDropped,
            "symmetric_half_dropped": SymmetricHalfDropped}
