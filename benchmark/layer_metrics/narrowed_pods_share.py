"""Device pipeline: of the pods that the traced waves' pops took into a
device batch, the share taken into a batch of a NARROWED plan (a template
whose NodeAffinity PreFilterResult names its nodes, so the plan is over
those rows only), in %. Read from the program's `sched.queue.pop` spans in
this run's trace, each of which the program closes with the stats `pods`
(the pods that pop accepted) and `narrowed` (those of them whose session
plans over a narrowed row set; 0 elsewhere). 100 in a wave of DaemonSet
pods: the sign that the mechanism, and not a bypass (the host path takes
no pop of a batch), placed them. A program whose pop spans carry no such
stat (the parent of the PR that added it), a run without a trace, and
traced waves whose pops took no pod read nothing."""

import progspans
import spanstats


def share(bench, pods, narrowed, waves):
    took = [float(p) for p in spanstats.in_traced_waves(bench, pods, waves)
            if p is not None]
    said = [n for n in spanstats.in_traced_waves(bench, narrowed, waves)
            if n is not None]
    if not sum(took) or not said:
        return None
    return 100.0 * sum(float(n) for n in said) / sum(took)


def read(obs):
    name = progspans.PREFIX + "queue.pop"
    pods = spanstats.this_runs(obs, name, "pods")
    narrowed = spanstats.this_runs(obs, name, "narrowed") if pods else None
    if not pods or not narrowed:
        return None
    return share(pods[0], pods[1], narrowed[1], int(obs["traced"]["waves"]))
