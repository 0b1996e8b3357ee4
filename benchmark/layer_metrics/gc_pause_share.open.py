"""Host scheduler loop, served: seconds the cyclic collector stopped the
scheduler's process (`scheduler_gc_pause_seconds_total`, all generations,
window delta) over the window's seconds."""

import progspans


def read(obs):
    return progspans.counter_share(obs, "scheduler_gc_pause_seconds_total")
