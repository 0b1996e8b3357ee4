"""The least bytes one call of the preemption dry run must move, from shapes
alone (`kernelcost.py`'s rule for the kernel `dry_run_preemption`,
`kubernetes_tpu/ops/kernel.py`).

One call answers, for every node row at once, whether the preemptor would
fit once the pods of lower priority had left and which of them must go:
whatever it does inside, it has to read each victim's request once, the
per-node quantities the fit check depends on once, and write one verdict a
node and one bit a victim slot. Integer quantities are 64-bit, as the
configuration's arithmetic is; masks are one byte an element.

    read  per node and victim slot: the victim's request over the resource
                    slots (r x int64) and whether the slot is taken (1)
    read  per node: allocatable and requested over the resource slots
                    (2 x r x int64), allocatable pods and pod count
                    (2 x int32), whether the row is a node (1)
    write per node: feasible (1) and the victim mask (k x 1)
    read  once    : the preemptor's request (r x int64)

`rows`, `k` and `r` are the shapes the run had: the program says them on its
`sched.postfilter.preempt` span (the padded row count of the device state,
the victim slots a node, the resource slots). A floor on traffic, not what
the kernel moves: the share of the roofline it yields says how far the dry
run is from being memory-bound. It has no matrix product; its operations (a
few comparisons an element read) stand in no peak of the chip's, so the
bytes are the roofline.
"""

from __future__ import annotations

import kernelcost

I64, I32, MASK = kernelcost.I64, kernelcost.I32, 1


def least_bytes_per_call(rows: int, k: int, r: int) -> int:
    read = (rows * k * (r * I64 + MASK)
            + rows * (2 * r * I64 + 2 * I32 + MASK) + r * I64)
    write = rows * (1 + k) * MASK
    return read + write


def hbm_roofline_share(kernel_s: float, calls: int, rows: int, k: int,
                       r: int, device_kind: str) -> float:
    """Percent: least time at peak HBM bandwidth over measured kernel time."""
    least_s = (calls * least_bytes_per_call(rows, k, r)
               / kernelcost.peaks(device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s
