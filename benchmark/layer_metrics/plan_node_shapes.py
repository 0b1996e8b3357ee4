"""Feature build and mirror: the distinct allocatable shapes (cpu, memory,
pod count) among the nodes of the plans built in the traced waves, as a mean
over their `sched.plan.build` spans, each of which the program closes with
the stat `node_shapes` (its mirror's census as the build ended: no pass over
the rows). A guard on the deployment more than a cost, as
`backlog_at_pop_mean` is on the traffic: 4.0 on a cluster of four node pools
whatever the program does, 1.0 where every node is alike, and something in
between where a cluster lost or gained a pool inside the traced waves. A
program whose build spans carry no such stat (the parent of the PR that added
it), a run without a trace, and traced waves without a build read nothing."""

import progspans
import spanstats


def mean(bench, found, waves):
    said = [float(n) for n in spanstats.in_traced_waves(bench, found, waves)
            if n is not None]
    return sum(said) / len(said) if said else None


def read(obs):
    got = spanstats.this_runs(obs, progspans.PREFIX + "plan.build",
                              "node_shapes")
    return mean(*got, int(obs["traced"]["waves"])) if got else None
