"""Feature build and mirror: self time of the program's `sched.plan.ipa`
spans (the build of the required inter-pod term tables inside a full plan
build: `anti_counts`, `exist_anti` and the affinity twins, one `term.matches`
a pod and term) in the traced waves, over their wave time. A program without
that stage (the parent of the PR that added it), and a cell whose traced waves
never opened it, read nothing."""

import progspans


def read(obs):
    got = progspans.stage_seconds(obs)
    if not got or "plan.ipa" not in got["self_s"]:
        return None
    return progspans.stage_share(obs, "plan.ipa")
