"""Device pipeline: share of the window's bound pods that the score-hint
walk placed on the host without a device dispatch (`hint_hits`)."""


def read(obs):
    c, w = obs.get("counters", {}), obs.get("window", {})
    if "hint_hits" not in c or not w.get("pods"):
        return None
    return 100.0 * c["hint_hits"] / w["pods"]
