"""Control plane and host scheduler loop, served: the median of
`scheduler_inbox_oldest_wait_seconds` over the window, in ms at bucket
resolution (the scheduler's /metrics delta): how long the oldest watch event
that the reflector thread had parked had waited when the loop began the drain
that replayed it, one observation a drain. It lies before queue admission,
where `scheduler_e2e_scheduling_duration_seconds` starts. A program without
the series (the parent of the PR that added it) reads nothing."""

import prom

NAME = "scheduler_inbox_oldest_wait_seconds"


def read(obs):
    series = (obs.get("prom") or {}).get("scheduler") or {}
    got = prom.quantile(series, NAME, 0.5)
    return None if got is None else 1e3 * got
