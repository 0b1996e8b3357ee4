"""Feature build and mirror: self time of the program's `sched.plan.adopt`
spans (a session's end: the snapshot's refresh and the mirror adopting the
carry, or its invalidation) in the traced waves, over their wave time."""

import progspans


def read(obs):
    return progspans.stage_share(obs, "plan.adopt")
