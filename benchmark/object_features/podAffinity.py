"""Preferred pod affinity on the program's pod: one weighted term a term, in
the shape `reference_features/` of the same name states. The builder's
`pod_affinity` takes no namespace list, so the weighted term it made is
replaced by one whose term carries the list (the terms are frozen
dataclasses), as `podAntiAffinity.py` does for required terms."""

import dataclasses

PREFERRED = "preferredDuringSchedulingIgnoredDuringExecution"


def apply(builder, value, template: dict):
    for wt in value.get(PREFERRED, ()):
        t = wt["podAffinityTerm"]
        builder = builder.pod_affinity(
            t["topologyKey"], t["labelSelector"]["matchLabels"],
            weight=int(wt["weight"]))
        namespaces = tuple(t.get("namespaces") or ())
        if namespaces:
            pod = builder.obj()
            aff = pod.affinity.pod_affinity
            last = aff.preferred[-1]
            last = dataclasses.replace(last, term=dataclasses.replace(
                last.term, namespaces=namespaces))
            pod.affinity = dataclasses.replace(
                pod.affinity, pod_affinity=dataclasses.replace(
                    aff, preferred=aff.preferred[:-1] + (last,)))
    return builder
