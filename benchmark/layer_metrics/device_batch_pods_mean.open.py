"""Device pipeline, served: pods bound in the window over the device batches
dispatched in it, from the scheduler's /metrics (window deltas: the count of
`scheduler_e2e_scheduling_duration_seconds`, which every bound pod feeds
once, over `scheduler_device_batches_total`, every engine summed; a program
from before the series had its `engine` label has the one unlabelled series):
how many pods an arrival shares a dispatch with. 1 where every pod is a
session of its own; the scheduler's batch cap where a backlog is drained.
Nothing to read in a window with no device batch (hints bound every pod)."""

import prom

BATCHES = "scheduler_device_batches_total"


def read(obs):
    series = (obs.get("prom") or {}).get("scheduler")
    if not series:
        return None
    batches = prom.total(series, BATCHES)
    if batches <= 0:
        return None
    return prom.total(
        series, "scheduler_e2e_scheduling_duration_seconds_count") / batches
