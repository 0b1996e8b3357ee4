"""Host scheduler loop: of the pods that the traced waves' pops took into a
device batch, the share taken as a run, on the session template's verdict and
not through the full per-pod check, in %. Read from the program's
`sched.queue.pop` spans in this run's trace, each of which the program closes
with the stats `pods` (the pods that pop accepted) and `run` (those of them
accepted as a run; the same two numbers count
`scheduler_queue_popped_pods_total{how}`). A wave of clones of one template
reads all but the head of each session; pods decoded from the wire read 0. A
program whose pop spans carry no such stats (the parent of the PR that added
them), a run without a trace, and traced waves whose pops took no pod (a
hint-bound wave) read nothing."""

import progspans
import spanstats


def share(bench, pods, run, waves):
    took = [float(p) for p in spanstats.in_traced_waves(bench, pods, waves)
            if p is not None]
    if not sum(took):
        return None
    as_run = [float(r) for r in spanstats.in_traced_waves(bench, run, waves)
              if r is not None]
    return 100.0 * sum(as_run) / sum(took)


def read(obs):
    name = progspans.PREFIX + "queue.pop"
    pods = spanstats.this_runs(obs, name, "pods")
    run = spanstats.this_runs(obs, name, "run") if pods else None
    if not pods or not run:
        return None
    return share(pods[0], pods[1], run[1], int(obs["traced"]["waves"]))
