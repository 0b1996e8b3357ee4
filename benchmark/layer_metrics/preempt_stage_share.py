"""Host scheduler loop: the share of the traced waves' clock that lies under
the program's `sched.postfilter.preempt` spans (the PostFilter of every
failed attempt, whole: eligibility, the dry run over every node, picking the
node, the host verify of the device's candidate, the evictions), in %. The
guard that the mechanism the cell exists for is most of its wave. The
attempts' parts, as the program says them, stand on a `[preempt]` line and
in `obs["preempt_attempt_parts"]`. Nothing on a program whose stage does not
say how the attempt ended (`nominated`: the parent of the PR that added it),
in a run without a trace, and where the traced waves hold no such span."""

import preemptspans


def read(obs):
    got = preemptspans.of(obs)
    if not got or got["wave_s"] <= 0:
        return None
    attempts = [e for e in preemptspans.stage(got["spans"],
                                              "postfilter.preempt")
                if "nominated" in e[3]]
    if not attempts:
        return None
    seconds = sum(dur for _s, _t, dur, _stats in attempts) / 1e9
    parts = preemptspans.mean_stats(attempts)
    parts["attempts"] = len(attempts)
    parts["postfilter_ms"] = round(1e3 * seconds / len(attempts), 3)
    parts["engine"] = sorted({str(e[3].get("engine", "")) for e in attempts})
    obs["preempt_attempt_parts"] = parts
    print(f"[preempt] attempts that ended in a nomination in the traced "
          f"waves, mean of what each says: {parts}", flush=True)
    return 100.0 * seconds / got["wave_s"]
