"""Device pipeline: self time of the program's `sched.device.dispatch` spans
(enqueueing a batch's kernel and its copy back) in the traced waves, over
their wave time."""

import progspans


def read(obs):
    return progspans.stage_share(obs, "device.dispatch")
