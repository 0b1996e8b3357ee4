"""Kernels: the least bytes the traced batches of the normalising scan must
move (ipacost.py, from shapes: the fit lanes plus per node the hostname value
and `ipa_base`, and each landing axis's delta row read and written) at the
chip's peak HBM bandwidth (peaks.json), over their measured kernel time
(`tracereduce.kernel_time`: the jitted scheduling programs in the traced
waves). Nothing to read in a rehearsal (no chip), where no batch was
dispatched, or where the cell's measured pods carry no preferred pod-affinity
term that a landing moves."""

import anticost
import ipacost
import tracereduce


def read(obs):
    got = tracereduce.kernel_time(obs)
    cluster = obs.get("cluster")
    if got is None or not cluster or obs.get("device", {}).get("rehearsal"):
        return None
    template = anticost.measured_template()
    axes = ipacost.landing_axes(template) if template else 0
    if not axes:
        return None
    seconds, batches = got
    pods = obs["traced"]["counters"].get("device_scheduled", 0) / batches
    return ipacost.ipa_hbm_roofline_share(
        seconds, batches, cluster["nodes"], pods, axes, obs["device"]["kind"])
