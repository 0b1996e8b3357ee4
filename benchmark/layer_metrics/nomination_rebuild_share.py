"""Feature build and mirror: the share of the traced waves' clock in the
`sched.plan.build` spans whose `cause` is `nomination` (the kept plan is the
same template's and nothing but the nomination set it was built under has
moved: what a plan that survives a nomination would save) and in the
`sched.plan.adopt` spans of their sessions, in %. Every cause stands beside
it on a `[preempt]` line and in `obs["rebuilds_by_cause"]`. Nothing on a
program that does not say the cause or has no nominated retry on the device
path (no `sched.nominated.eval` span: the parent of the PR that added label
and stage), and in a run without a trace."""

import churnspans
import preemptspans


def read(obs):
    got = preemptspans.of(obs)
    if not got or got["wave_s"] <= 0:
        return None
    by_cause = churnspans.builds_by_cause(got["spans"])
    if not by_cause or not preemptspans.stage(got["spans"], "nominated.eval"):
        return None
    obs["rebuilds_by_cause"] = by_cause
    shares = {c: [n, round(100.0 * s / got["wave_s"], 2)]
              for c, (n, s) in sorted(by_cause.items())}
    print(f"[preempt] full builds in the traced waves by cause, [builds, % "
          f"of wave time in plan.build + plan.adopt]: {shares}", flush=True)
    return 100.0 * by_cause.get("nomination", [0, 0.0])[1] / got["wave_s"]
