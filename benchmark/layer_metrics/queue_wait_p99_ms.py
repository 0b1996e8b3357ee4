"""Host scheduler loop, served: 99th percentile of queue admission to pop
over every pod of the window, from the scheduler's /metrics
(`scheduler_pod_stage_duration_seconds{stage="queue.wait"}`, window delta,
bucket resolution)."""

import progspans


def read(obs):
    return progspans.pod_stage_quantile_ms(obs, "queue.wait", 0.99)
