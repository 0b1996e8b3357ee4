"""Device pipeline: of the scheduling programs' device seconds in the traced
waves (`timeline.py`: the runs of `tracereduce.SCHEDULING_PROGRAMS` on the
first device plane, from this run's profiler trace), the share that lies
outside every `sched.device.wait` interval of the program's loop, in %: the
part of the kernels that host work covered. 100: the host never waited while
a kernel ran; 0: every kernel second was a second the loop stood still. No
join is needed (interval overlap), so it reads on a program that stamps no
`seq` as well. Nothing to read without a trace of waves, or where no
scheduling program ran in them (the hints bound every pod)."""

import timeline


def read(obs):
    tl = timeline.of(obs)
    if not tl or tl["kernel_s"] <= 0:
        return None
    return 100.0 * tl["hidden_s"] / tl["kernel_s"]
