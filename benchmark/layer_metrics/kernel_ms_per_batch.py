"""Kernels: summed device time of the jitted scheduling programs in the
traced waves, from the profiler trace, over the device batches dispatched in
those waves. Nothing to read where no batch was dispatched."""

import tracereduce


def read(obs):
    got = tracereduce.kernel_time(obs)
    if got is None:
        return None
    seconds, batches = got
    return 1e3 * seconds / batches
