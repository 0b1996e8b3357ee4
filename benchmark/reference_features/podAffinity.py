"""Preferred pod affinity over the hostname: InterPodAffinity's score.

Template value, in the shape of the pod spec:

    {"preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": 1,
         "podAffinityTerm": {
             "labelSelector": {"matchLabels": {"color": "red"}},
             "topologyKey": "kubernetes.io/hostname",
             "namespaces": ["sched-1", "sched-0"]}}]}

``namespaces`` is optional; without it a term selects in its owner's own
namespace (the template's ``namespace`` key, default ``default``).

Semantics (kube-scheduler ``interpodaffinity/scoring.go``, default plugin
arguments: ``hardPodAffinityWeight`` 1, which only required terms feed, and
``ignorePreferredTermsOfExistingPods`` false): a term of pod ``a`` selects pod
``b`` when ``b``'s namespace is among the term's and every ``matchLabels`` pair
equals ``b``'s label. With the hostname as topology key a domain is one node,
so the raw score of a node is the sum, over the pods on it, of

- the weights of the incoming pod's preferred terms that select the pod
  (``processExistingPod``, the incoming pod's own terms), and
- the weights of the pod's own preferred terms that select the incoming pod
  (the symmetric half: a pod already there pulls the one coming).

``NormalizeScore`` then runs over the *kept* rows (the feasible nodes of the
adaptive sample, the only ones that reach the score phase) in the published
form, float then truncate:

    int64(100 * (float64(raw - min) / float64(max - min)))

and 0 for every row where ``max == min``. The framework multiplies by the
plugin's weight, 2 in the default set. Preferred terms filter nothing, so
``feasible`` has no say.

Refused as ``Unmodelled``: required affinity terms (they filter, and feed the
score through ``hardPodAffinityWeight``), a topology key other than the
hostname (domains wider than a node), ``matchExpressions``, an empty or
missing ``matchLabels`` (select-all and select-none), namespace selectors,
``matchLabelKeys`` / ``mismatchLabelKeys``, a weight outside 1-100 (the API
refuses it), and any other key. Pod *anti*-affinity is another template key.

Controls (``control.py`` finds them as ``podAffinity.<name>``):
``score_dropped`` scores nothing; ``plugin_weight_1`` leaves the plugin's
weight out; ``symmetric_half_dropped`` reads only the incoming pod's own
terms; ``normalised_over_cluster`` takes min and max over every node and not
over the kept rows; ``floor_not_float`` normalises by integer floor division
(``100 * (raw - min) // (max - min)``), which parts from the published form
first at 29 / 50 (57 against 58).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from reference import MAX_NODE_SCORE, Unmodelled

KEY = "podAffinity"
HOSTNAME_KEY = "kubernetes.io/hostname"
PREFERRED = "preferredDuringSchedulingIgnoredDuringExecution"
WEIGHTED_KEYS = {"weight", "podAffinityTerm"}
TERM_KEYS = {"labelSelector", "topologyKey", "namespaces"}
PLUGIN_WEIGHT = 2                       # InterPodAffinity in the default set


def parse(value, template: dict) -> list:
    """[(weight, namespaces or None, selector)], the selector as sorted
    (label, value) pairs."""
    unknown = set(value) - {PREFERRED}
    if unknown:
        raise Unmodelled(f"{KEY} keys {sorted(unknown)}")
    terms = []
    for wt in value.get(PREFERRED, ()):
        unknown = set(wt) - WEIGHTED_KEYS
        if unknown:
            raise Unmodelled(f"weighted affinity term keys {sorted(unknown)}")
        weight = wt.get("weight")
        if not isinstance(weight, int) or isinstance(weight, bool) \
                or not 1 <= weight <= 100:
            raise Unmodelled(f"affinity term weight {weight!r}")
        t = wt.get("podAffinityTerm") or {}
        unknown = set(t) - TERM_KEYS
        if unknown:
            raise Unmodelled(f"affinity term keys {sorted(unknown)}")
        if t.get("topologyKey") != HOSTNAME_KEY:
            raise Unmodelled(f"affinity over {t.get('topologyKey')!r}")
        selector = t.get("labelSelector") or {}
        if set(selector) != {"matchLabels"} or not selector["matchLabels"]:
            raise Unmodelled(f"affinity label selector {selector!r}")
        namespaces = t.get("namespaces")
        if namespaces is not None and not namespaces:
            raise Unmodelled("an empty namespaces list")
        terms.append((weight, frozenset(namespaces) if namespaces else None,
                      tuple(sorted(selector["matchLabels"].items()))))
    return terms


def _namespace(pod) -> str:
    return pod.features.get("namespace", "default")


def _pull(owner, target) -> int:
    """Summed weight of `owner`'s terms that select `target`."""
    total = 0
    for weight, namespaces, selector in owner.features.get(KEY, ()):
        if (_namespace(target) in (namespaces or (_namespace(owner),))
                and all(target.labels.get(k) == v for k, v in selector)):
            total += weight
    return total


class State:
    """Pods on each node, one count vector for each pod template met."""

    plugin_weight = PLUGIN_WEIGHT

    def __init__(self, ref):
        self.n = ref.n
        self._on: Dict[object, np.ndarray] = {}         # pod shape -> count[n]
        self._pulls: Dict[tuple, int] = {}

    def pull(self, there, incoming) -> int:
        """What one pod `there` on a node adds to that node's raw score."""
        return _pull(incoming, there) + _pull(there, incoming)

    def account(self, row: int, pod, sign: int) -> None:
        on = self._on.get(pod)
        if on is None:
            on = self._on[pod] = np.zeros(self.n, np.int64)
        on[row] += sign

    def feasible(self, pod):
        return None

    def raw(self, pod) -> np.ndarray:
        """The raw score of every node for `pod`."""
        raw = np.zeros(self.n, np.int64)
        for there, on in self._on.items():
            w = self._pulls.get((there, pod))
            if w is None:
                w = self._pulls[there, pod] = self.pull(there, pod)
            if w:
                raw += w * on
        return raw

    def extremes(self, raw: np.ndarray, rows: np.ndarray) -> tuple:
        """(min, max) that NormalizeScore spans: those of the kept rows."""
        kept = raw[rows]
        return int(kept.min()), int(kept.max())

    def normalise(self, above: np.ndarray, span: int) -> np.ndarray:
        """scoring.go's own form: float64, then truncated."""
        return (MAX_NODE_SCORE
                * (above.astype(np.float64) / np.float64(span))
                ).astype(np.int64)

    def score(self, pod, rows):
        raw = self.raw(pod)
        low, high = self.extremes(raw, rows)
        if high == low:
            return np.zeros(len(rows), np.int64)
        return self.plugin_weight * self.normalise(raw[rows] - low, high - low)


class ScoreDropped(State):
    def score(self, pod, rows):
        return None


class PluginWeight1(State):
    plugin_weight = 1


class SymmetricHalfDropped(State):
    def pull(self, there, incoming) -> int:
        return _pull(incoming, there)


class NormalisedOverCluster(State):
    def extremes(self, raw, rows) -> tuple:
        return int(raw.min()), int(raw.max())


class FloorNotFloat(State):
    def normalise(self, above, span):
        return MAX_NODE_SCORE * above // span


CONTROLS = {"score_dropped": ScoreDropped,
            "plugin_weight_1": PluginWeight1,
            "symmetric_half_dropped": SymmetricHalfDropped,
            "normalised_over_cluster": NormalisedOverCluster,
            "floor_not_float": FloorNotFloat}
