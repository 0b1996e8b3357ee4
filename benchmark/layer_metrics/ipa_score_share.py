"""Feature build and mirror: self time of the program's `sched.plan.ipa_score`
spans (the walk that builds the InterPodAffinity score tables of a full plan
build, `ipa_base`, `ipa_axis`, `ipa_wland`: every pod of the cluster against
the incoming pod's preferred terms, and each existing pod's own preferred
terms against the incoming pod) in the traced waves, over their wave time. A
program without that stage (the parent of the PR that added it), and a cell
whose traced waves never opened it, read nothing."""

import progspans

STAGE = "plan.ipa_score"


def read(obs):
    got = progspans.stage_seconds(obs)
    if not got or STAGE not in got["self_s"]:
        return None
    return progspans.stage_share(obs, STAGE)
