"""Host scheduler loop: of the batches retired in the traced waves, the share
whose host tail committed every pod in passes over the batch (one assume, one
bulk bind, one settle) and not by one call a pod, in %. Read from the
program's `sched.host.commit` spans in this run's trace, each of which the
program opens with the stat `tail`: `batch`, `single`, or `mixed` where the
passes ended early (the same decision counts
`scheduler_commit_pods_total{tail}`). A program whose commit spans carry no
tail (the parent of the PR that added the stat), a run without a trace, and
traced waves without a commit read nothing."""

import progspans
import spanstats


def share(bench, found, waves, tail="batch"):
    tails = [None if t is None else str(t)
             for t in spanstats.in_traced_waves(bench, found, waves)]
    if not tails or all(t is None for t in tails):
        return None
    return 100.0 * sum(t == tail for t in tails) / len(tails)


def read(obs):
    got = spanstats.this_runs(obs, progspans.PREFIX + "host.commit", "tail")
    return share(*got, int(obs["traced"]["waves"])) if got else None
