"""Host scheduler loop: what a failed attempt costs, in ms: the mean, over
the traced waves' `sched.postfilter.preempt` spans, of the turn of the loop
(`sched.cycle`) each lies in: the pod's pop, its session's plan build, the
dispatch and the wait that say "no node", the diagnosis, PostFilter with the
preemption dry run, and the session's adoption. Its parts stand beside it in
`obs["failed_attempt_parts"]` and on a `[churn]` line: the stage itself and
what the program says on it (`engine`, and for the device's dry run
`victims_ms`, `plan_ms`, `dispatch_ms`, `fetch_ms`), the turn's `plan.build`
and `plan.adopt`. A program without the stage (the parent of the PR that
added it), a run without a trace and traced waves without a failed attempt
read nothing."""

import churnspans


def parts(spans):
    attempts = [a for a in churnspans.failed_attempts(spans)
                if a["turn_ms"] is not None]
    if not attempts:
        return None
    keys = sorted({k for a in attempts for k, v in a.items()
                   if isinstance(v, float)})
    mean = {k: sum(a.get(k, 0.0) for a in attempts) / len(attempts)
            for k in keys}
    mean["attempts"] = len(attempts)
    mean["engine"] = sorted({str(a.get("engine", "")) for a in attempts})
    return mean


def read(obs):
    got = churnspans.of(obs)
    mean = parts(got["spans"]) if got else None
    if mean is None:
        return None
    obs["failed_attempt_parts"] = mean
    print(f"[churn] failed attempts in the traced waves, mean ms: "
          f"{ {k: round(v, 3) if isinstance(v, float) else v for k, v in mean.items()} }",
          flush=True)
    return mean["turn_ms"]
