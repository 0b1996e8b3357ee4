"""Feature build and mirror: the program's `plan_build_s` counter (snapshot
to features on the host) over the window's summed wave time."""


def read(obs):
    c, w = obs.get("counters", {}), obs.get("window", {})
    if "plan_build_s" not in c or not w.get("wave_s"):
        return None
    return 100.0 * c["plan_build_s"] / w["wave_s"]
