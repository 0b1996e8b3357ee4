"""Kernels: the least bytes the traced batches of a cell with an
anti-affinity lane must move (anticost.py, from shapes: the fit lanes plus the
hostname value, `exist_anti` and each term's count row) at the chip's peak HBM
bandwidth (peaks.json), over their measured kernel time
(`tracereduce.kernel_time`: the jitted scheduling programs in the traced
waves). Nothing to read in a rehearsal (no chip), where no batch was
dispatched, or where the cell's measured pods carry no required
anti-affinity term."""

import anticost
import tracereduce


def read(obs):
    got = tracereduce.kernel_time(obs)
    cluster = obs.get("cluster")
    if got is None or not cluster or obs.get("device", {}).get("rehearsal"):
        return None
    template = anticost.measured_template()
    terms = anticost.required_anti_terms(template) if template else 0
    if not terms:
        return None
    seconds, batches = got
    pods = obs["traced"]["counters"].get("device_scheduled", 0) / batches
    return anticost.anti_hbm_roofline_share(
        seconds, batches, cluster["nodes"], pods, terms,
        obs["device"]["kind"])
