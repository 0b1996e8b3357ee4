"""The toy feature on the program's pod: required and preferred node affinity
over the zone label."""

ZONE_KEY = "topology.kubernetes.io/zone"


def apply(builder, value, template):
    builder = builder.node_affinity_in(ZONE_KEY, sorted(value["required"]))
    if value.get("preferred"):
        builder = builder.preferred_node_affinity(
            int(value.get("weight", 1)), ZONE_KEY, sorted(value["preferred"]))
    return builder
