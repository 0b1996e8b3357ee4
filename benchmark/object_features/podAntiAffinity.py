"""Required pod anti-affinity on the program's pod: one required term a
term, in the shape `reference_features/` of the same name states. The
builder's `pod_affinity` takes no namespace list, so the term it made is
replaced by one that carries the list (the terms are frozen dataclasses)."""

import dataclasses

REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"


def apply(builder, value, template: dict):
    for t in value.get(REQUIRED, ()):
        builder = builder.pod_affinity(
            t["topologyKey"], t["labelSelector"]["matchLabels"], anti=True)
        namespaces = tuple(t.get("namespaces") or ())
        if namespaces:
            pod = builder.obj()
            anti = pod.affinity.pod_anti_affinity
            last = dataclasses.replace(anti.required[-1], namespaces=namespaces)
            pod.affinity = dataclasses.replace(
                pod.affinity, pod_anti_affinity=dataclasses.replace(
                    anti, required=anti.required[:-1] + (last,)))
    return builder
