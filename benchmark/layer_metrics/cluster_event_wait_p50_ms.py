"""Control plane and host scheduler loop: the median, over the window, of
`scheduler_cluster_event_wait_seconds{kind="node"}`, in ms at bucket
resolution: how long a node event that the client's thread parked in the
scheduler's inbox had waited when the loop replayed it (`inbox.wait` of one
event; the oldest-wait series has one observation a drain and mostly meets
pods). A program without the series (the parent of the PR that added it)
reads nothing."""

import prom

NAME = "scheduler_cluster_event_wait_seconds"


def read(obs):
    series = (obs.get("prom") or {}).get("scheduler") or {}
    nodes = {k: v for k, v in series.items() if ("kind", "node") in k[1]}
    got = prom.quantile(nodes, NAME, 0.5)
    return None if got is None else 1e3 * got
