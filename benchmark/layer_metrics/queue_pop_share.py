"""Host scheduler loop: self time of the program's `sched.queue.pop` spans
(popping, signing and matching a batch off the active queue) in the traced
waves, over their wave time."""

import progspans


def read(obs):
    return progspans.stage_share(obs, "queue.pop")
