"""Device pipeline: mean over the batches of the traced waves of the part of
the batch's `sched.device.wait` that lies after the end of its program run on
the device (end of the wait - end of the run; 0 where the wait ended first;
counted from the wait's start where the program had ended before the host
came to wait, since the time between the two was the host's own work), in ms:
the copy back and the wake-up, what a faster kernel cannot give back. Needs
the join of `timeline.py` (the k-th program run to the dispatch and the wait
of equal `seq`): a program whose spans carry no `seq` (the parent of the PR
that added it), traced waves whose counts of dispatches, waits and program
runs differ, and a run without a trace read nothing."""

import timeline


def read(obs):
    got = timeline.batches(timeline.of(obs))
    return sum(b["fetch_tail_ms"] for b in got) / len(got) if got else None
