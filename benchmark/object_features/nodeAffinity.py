"""Required node affinity that pins the program's pod to named nodes: one
`node_affinity_name`-shaped term a `nodeSelectorTerm`, each with the one
`matchFields metadata.name In [names]` requirement that
`reference_features/` of the same name states (and refuses everything
else, before this file is asked)."""

from kubernetes_tpu.api.labels import IN, Requirement
from kubernetes_tpu.api.types import (Affinity, NodeAffinity, NodeSelector,
                                      NodeSelectorTerm)

REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"


def apply(builder, value, template: dict):
    terms = tuple(
        NodeSelectorTerm(match_fields=tuple(
            Requirement(r["key"], IN, tuple(r["values"]))
            for r in term["matchFields"]))
        for term in value[REQUIRED]["nodeSelectorTerms"])
    pod = builder.obj()
    had = pod.affinity
    pod.affinity = Affinity(
        node_affinity=NodeAffinity(required=NodeSelector(terms)),
        pod_affinity=had.pod_affinity if had else None,
        pod_anti_affinity=had.pod_anti_affinity if had else None)
    return builder
