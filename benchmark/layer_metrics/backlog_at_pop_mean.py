"""Host scheduler loop: the mean depth of the active queue as a batch's pop
began, over the `sched.queue.pop` spans of the traced waves, in pods (the
program opens each such stage, one a batch, with the stat `backlog`). A guard
on the traffic more than a cost: creates are done a fraction of a second into
a wave, so a loop that works against the whole backlog reads about half a
wave's pods whatever it does, and one that is handed its pods a batch at a
time reads hundreds. A program whose pop spans carry no backlog (the parent
of the PR that added the stat), a run without a trace, and traced waves
without a pop read nothing."""

import progspans
import spanstats


def mean(bench, found, waves):
    met = [float(b) for b in spanstats.in_traced_waves(bench, found, waves)
           if b is not None]
    return sum(met) / len(met) if met else None


def read(obs):
    got = spanstats.this_runs(obs, progspans.PREFIX + "queue.pop", "backlog")
    return mean(*got, int(obs["traced"]["waves"])) if got else None
