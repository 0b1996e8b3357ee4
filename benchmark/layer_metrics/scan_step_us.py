"""Kernels: the scan programs' device seconds in the traced waves over the
steps their dispatches ran (the stat `steps` that each scan's
`sched.device.dispatch` carries: `n_active`, the trip count of
`schedule_batch`'s loop), in us a step. `kernel_ms_per_batch` mixes this with
the batch's size. Where every dispatch of the traced waves is a scan's, the
seconds are all the scheduling programs' (no join); where lap dispatches share
the waves, only the joined scan runs' (`timeline.py`). Nothing to read where no
dispatch carries `steps` (the lap kernel counts none), or without a trace."""

import timeline


def read(obs):
    tl = timeline.of(obs)
    if not tl or not tl["steps"] or not tl["scan_s"]:
        return None
    return 1e6 * tl["scan_s"] / tl["steps"]
