"""Host scheduler loop: self time of the program's `sched.inbox.drain` spans
(replaying the watch events parked by other threads, and classifying the
journal for a live session) in the traced waves, over their wave time."""

import progspans


def read(obs):
    return progspans.stage_share(obs, "inbox.drain")
