"""Feature build and mirror: the share of the traced waves' clock in the
`sched.plan.build` spans whose `cause` is `structural` (a kept plan of the
same template, made useless by a node added or removed since: what a row
patch for node events would save) and in the `sched.plan.adopt` spans of
their sessions, in %. The other causes stand beside it on a `[churn]` line
and in `obs["rebuilds_by_cause"]` (`other_pod`: the one kept plan was another
template's, here the churn pod's or, for the churn pod, the plain pods';
`journal_overrun`: the restore's deletes). A program whose spans say no cause
(the parent of the PR that added it) and a run without a trace read
nothing."""

import churnspans


def read(obs):
    got = churnspans.of(obs)
    if not got or got["wave_s"] <= 0:
        return None
    by_cause = churnspans.builds_by_cause(got["spans"])
    if not by_cause:
        return None
    obs["rebuilds_by_cause"] = by_cause
    shares = {c: [n, round(100.0 * s / got["wave_s"], 2)]
              for c, (n, s) in sorted(by_cause.items())}
    print(f"[churn] full builds in the traced waves by cause, [builds, % of "
          f"wave time in plan.build + plan.adopt]: {shares}", flush=True)
    return 100.0 * by_cause.get("structural", [0, 0.0])[1] / got["wave_s"]
