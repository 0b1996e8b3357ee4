"""Control plane and host scheduler loop, served: 99th percentile of the
bind call's round trip as the scheduler sees it, over every pod of the
window (`scheduler_pod_stage_duration_seconds{stage="bind.post"}`, window
delta, bucket resolution)."""

import progspans


def read(obs):
    return progspans.pod_stage_quantile_ms(obs, "bind.post", 0.99)
