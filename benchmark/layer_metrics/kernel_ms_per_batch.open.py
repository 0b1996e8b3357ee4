"""Kernels, served: summed device time of the jitted scheduling programs
inside the traced seconds of the window, from the profiler trace, over their
runs in the same trace (`tracereduce.reduce` keeps both for each program: the
chip's "XLA Modules" line holds one event for each run). The open driver
keeps no count of the batches dispatched while the profiler ran, and its
`/metrics` delta spans the whole window, so on the chip the runs are counted
where the seconds are; a run cut by an edge of the trace counts with the part
of it that lies inside. In a rehearsal the CPU's executor threads stand in
with one event for each operation, not each run: there the batches are the
`/metrics` delta's (`scheduler_device_batches_total`; a rehearsal's window is
no longer than its traced seconds), and the number is never a device number.
Nothing to read without a trace, or in a window whose pods the hints bound
(no scheduling program ran)."""

import prom
import tracereduce


def read(obs):
    reduced = (obs.get("traced") or {}).get("reduced")
    if not reduced:
        return None
    programs = [m for name, m in reduced["modules"].items()
                if name.startswith(tracereduce.SCHEDULING_PROGRAMS)]
    if obs.get("device", {}).get("rehearsal"):
        runs = prom.total((obs.get("prom") or {}).get("scheduler") or {},
                          "scheduler_device_batches_total")
    else:
        runs = sum(m["runs"] for m in programs)
    seconds = sum(m["seconds"] for m in programs)
    if runs <= 0 or seconds <= 0:
        return None
    return 1e3 * seconds / runs
