"""Host scheduler loop: what a nominated pod's retry costs, in ms: the mean,
over the traced waves' `sched.nominated.eval` spans (the evaluation of a
nominated pod's own node, first and alone, on the device path), of the turn
of the loop (`sched.cycle`) each lies in: the pod's pop, its own plan build
with every row but the nominated one masked, the dispatch and the wait, the
bind. The outcomes stand on a `[preempt]` line and in
`obs["nominated_retries"]`. Nothing on a program without the stage (the
parent of the PR that added it), in a run without a trace, and where the
traced waves hold no retry."""

import preemptspans


def read(obs):
    got = preemptspans.of(obs)
    if not got:
        return None
    evals = preemptspans.stage(got["spans"], "nominated.eval")
    turns = [preemptspans.turn_ms(got["spans"], e) for e in evals]
    turns = [t for t in turns if t is not None]
    if not turns:
        return None
    outcomes = {}
    for e in evals:
        key = str(e[3].get("outcome", ""))
        outcomes[key] = outcomes.get(key, 0) + 1
    told = {"retries": len(evals), "outcomes": outcomes,
            "turn_ms": round(sum(turns) / len(turns), 3),
            "eval_ms": round(sum(e[2] for e in evals) / len(evals) / 1e6, 3)}
    obs["nominated_retries"] = told
    print(f"[preempt] nominated retries in the traced waves: {told}",
          flush=True)
    return sum(turns) / len(turns)
