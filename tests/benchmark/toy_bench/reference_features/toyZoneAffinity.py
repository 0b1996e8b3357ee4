"""A toy pod feature for the tests, with a filter and a score: node affinity
over the zone label. Value: ``{"required": [zones], "preferred": [zones],
"weight": w}``. A node outside ``required`` is refused (NodeAffinity's
filter); a node in ``preferred`` scores ``w``, normalised to 100 over the
candidate rows and weighted 2, as the default plugin set does."""

import numpy as np

from reference import Unmodelled

KEY = "toyZoneAffinity"
PLUGIN_WEIGHT = 2


def parse(value, template):
    unknown = set(value) - {"required", "preferred", "weight"}
    if unknown:
        raise Unmodelled(f"{KEY} keys {sorted(unknown)}")
    return (frozenset(value["required"]), frozenset(value.get("preferred", ())),
            int(value.get("weight", 1)))


class State:
    def __init__(self, ref):
        self.zone_name = np.array(ref.zones)[ref.zone_of]

    def _in(self, zones):
        return np.isin(self.zone_name, sorted(zones))

    def feasible(self, pod):
        terms = pod.features.get(KEY)
        return None if terms is None else self._in(terms[0])

    def score(self, pod, rows):
        terms = pod.features.get(KEY)
        if terms is None:
            return None
        raw = self._in(terms[1])[rows].astype(np.int64) * terms[2]
        top = raw.max()
        return PLUGIN_WEIGHT * (raw * 100 // top if top > 0 else raw)

    def account(self, row, pod, sign):
        pass
