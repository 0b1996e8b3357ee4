"""Control plane and host scheduler loop: the scheduler's own share of the
latency (queue admission to bound), the 99th percentile of the window delta
of `scheduler_e2e_scheduling_duration_seconds` on its /metrics, at bucket
resolution. What is left of bind_p99_ms above it is the control plane's."""

import prom


def read(obs):
    series = (obs.get("prom") or {}).get("scheduler")
    if not series:
        return None
    q = prom.quantile(series, "scheduler_e2e_scheduling_duration_seconds", 0.99)
    return None if q is None else 1e3 * q
