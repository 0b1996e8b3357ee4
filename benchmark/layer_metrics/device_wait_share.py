"""Device pipeline: the program's `device_wait_s` counter (time the host
blocked on a device result fetch: not kernel time) over the window's summed
wave time."""


def read(obs):
    c, w = obs.get("counters", {}), obs.get("window", {})
    if "device_wait_s" not in c or not w.get("wave_s"):
        return None
    return 100.0 * c["device_wait_s"] / w["wave_s"]
