"""Device pipeline, served: share of the window's bound pods that the
score-hint walk placed on the host with no device dispatch, from the
scheduler's /metrics (window deltas: `scheduler_hint_cache_hits_total` over
the count of `scheduler_e2e_scheduling_duration_seconds`, which every bound
pod feeds once)."""

import prom


def read(obs):
    series = (obs.get("prom") or {}).get("scheduler")
    if not series:
        return None
    bound = prom.total(series, "scheduler_e2e_scheduling_duration_seconds_count")
    if bound <= 0:
        return None
    return 100.0 * prom.total(series, "scheduler_hint_cache_hits_total") / bound
