"""Host scheduler loop: self time of the program's `sched.gc.pause` spans (a
collection of the cyclic collector that the loop's own thread ran inside one
of its stages; the seconds leave that stage's self time) in the traced waves,
over their wave time, in %. The inside twin of `gc_pause_share`, which times
every thread's collections from outside over the whole window. A traced wave
may see no collection and then reads 0; a program that does not book pauses
must read nothing, so the reader wants the sign that it does: the loop's
`sched.cycle` span opened with the stat `pauses` (`timeline.py` keeps it)."""

import progspans
import timeline


def read(obs):
    tl = timeline.of(obs)
    if not tl or not tl["books_pauses"]:
        return None
    return progspans.stage_share(obs, "gc.pause")
