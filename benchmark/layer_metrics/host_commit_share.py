"""Host scheduler loop: the program's `host_commit_s` counter (assume,
reserve, permit, bind tails) over the window's summed wave time."""


def read(obs):
    c, w = obs.get("counters", {}), obs.get("window", {})
    if "host_commit_s" not in c or not w.get("wave_s"):
        return None
    return 100.0 * c["host_commit_s"] / w["wave_s"]
