"""Host scheduler loop, served: seconds the scheduler binary spent in its
idle sleep and lease ticks (`scheduler_loop_stage_seconds_total
{stage="loop.idle"}`, window delta) over the window's seconds."""

import progspans


def read(obs):
    return progspans.counter_share(
        obs, "scheduler_loop_stage_seconds_total", stage="loop.idle")
