"""Host scheduler loop: the traced waves' time that lies under no named
stage of the program's loop (under no `sched.*` span, or under `sched.cycle`
alone) over their wave time. What the program cannot put on a function."""

import progspans


def read(obs):
    got = progspans.stage_seconds(obs)
    if not got or got["wave_s"] <= 0:
        return None
    return 100.0 * got["unnamed_s"] / got["wave_s"]
