"""Kernels: the least bytes the traced batches must move (kernelcost.py, from
shapes) at the chip's peak HBM bandwidth (peaks.json), over their measured
kernel time. Bound by memory bandwidth by construction: it says how far from
memory-bound the int64 scan is. A rehearsal has no chip and reads nothing."""

import kernelcost
import tracereduce


def read(obs):
    got = tracereduce.kernel_time(obs)
    cluster = obs.get("cluster")
    if got is None or not cluster or obs.get("device", {}).get("rehearsal"):
        return None
    seconds, batches = got
    pods = obs["traced"]["counters"].get("device_scheduled", 0) / batches
    return kernelcost.hbm_roofline_share(
        seconds, batches, cluster["nodes"], pods, cluster["zones"],
        obs["device"]["kind"])
