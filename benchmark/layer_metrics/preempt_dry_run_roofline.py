"""Kernels: the least bytes the traced dry runs of preemption must move
(preemptcost.py, from the shapes the program says on its
`sched.postfilter.preempt` spans: rows, victim slots, resource slots) at the
chip's peak HBM bandwidth (peaks.json), over the measured device time of the
program `jit_dry_run_preemption` in the trace (XLA's module names carry a
fingerprint behind the name on the chip: found by the name's beginning, as
`tracereduce.kernel_time` finds the scheduling programs). Nothing to read in a rehearsal
(no chip), where the trace holds no run of the program, or where the spans
say no shapes (the parent of the PR that added them)."""

import churnspans
import preemptcost

PROGRAM = "jit_dry_run_preemption"


def read(obs):
    reduced = (obs.get("traced") or {}).get("reduced") or {}
    ran = [m for name, m in reduced.get("modules", {}).items()
           if name.startswith(PROGRAM)]
    seconds = sum(m["seconds"] for m in ran)
    got = churnspans.of(obs)
    if not seconds or not got or obs.get("device", {}).get("rehearsal"):
        return None
    shapes = {(int(float(s["rows"])), int(float(s["k"])), int(float(s["r"])))
              for stage, _start, _dur, s in got["spans"]
              if stage == "postfilter.preempt" and "rows" in s}
    if len(shapes) != 1:
        return None
    rows, k, r = shapes.pop()
    return preemptcost.hbm_roofline_share(
        seconds, sum(m["runs"] for m in ran), rows, k, r,
        obs["device"]["kind"])
