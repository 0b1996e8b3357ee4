"""Hard zone spread on the program's pod: one `spread_constraint` a
constraint, with the defaults `reference_features/` of the same name states
(maxSkew 1, the zone label, DoNotSchedule, the pod's own labels)."""

ZONE_KEY = "topology.kubernetes.io/zone"


def apply(builder, value, template: dict):
    for c in value:
        builder = builder.spread_constraint(
            c.get("maxSkew", 1), c.get("topologyKey", ZONE_KEY),
            c.get("whenUnsatisfiable", "DoNotSchedule"),
            c.get("labelSelector", template.get("labels", {})))
    return builder
