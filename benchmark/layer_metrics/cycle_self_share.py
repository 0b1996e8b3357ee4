"""Host scheduler loop: the own self time of the program's `sched.cycle` spans
(a turn of the loop outside every stage it opens: the turn's bookkeeping) in
the traced waves, over their wave time. `loop_unnamed_share` minus this is the
wave time under no `sched.*` span at all (the driver's own turns between two
calls into the loop)."""

import progspans


def read(obs):
    return progspans.stage_share(obs, "cycle")
