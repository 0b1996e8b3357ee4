"""Control plane and host scheduler loop, served: pods a binding request
carried, in the mean over the window, from the scheduler's /metrics (window
deltas: `scheduler_bind_request_pods_total` over
`scheduler_bind_requests_total`, single and bulk requests summed). 1 where
every bind is a request of its own; up to the dispatcher's batch cap where
queued binds go out in bulk. Nothing to read on a program without the two
counters, or in a window with no binding request."""

import prom


def read(obs):
    series = (obs.get("prom") or {}).get("scheduler")
    if not series:
        return None
    requests = prom.total(series, "scheduler_bind_requests_total")
    if requests <= 0:
        return None
    return prom.total(series, "scheduler_bind_request_pods_total") / requests
