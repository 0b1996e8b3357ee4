"""The least bytes one scheduling batch must move, from shapes alone.

A batch of pods is placed against the node state: whatever the kernel does
inside, it has to read the per-node quantities the decision depends on once,
write back the ones a placement changes once, read the pod batch and write
one result per pod. Integer quantities are 64-bit as the configuration's
arithmetic is (memory in bytes overflows 32 bits); indices are 32-bit.

    read  per node: allocatable cpu, memory, pods; requested cpu, memory;
                    non-zero requested cpu, memory; pod count (8 x int64)
                    + zone index (int32)
    write per node: requested cpu, memory; non-zero requested cpu, memory;
                    pod count (5 x int64)
    per zone      : matching-pod count of the spread selector, read and
                    written (2 x int64), where the pods carry a constraint
    per pod       : request cpu, memory read (2 x int64), node index written
                    (int32)

This is a floor on traffic, not what the kernel moves: the share of the
roofline it yields says how far the scan is from being memory-bound.
"""

from __future__ import annotations

import json
import os

I64, I32 = 8, 4


def least_bytes_per_batch(nodes: int, pods: int, zones: int = 0) -> int:
    read = nodes * (8 * I64 + I32) + pods * 2 * I64 + zones * I64
    write = nodes * 5 * I64 + pods * I32 + zones * I64
    return read + write


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return table[device_kind]


def hbm_roofline_share(kernel_s: float, batches: int, nodes: int, pods: int,
                       zones: int, device_kind: str) -> float:
    """Percent: least time at peak HBM bandwidth over measured kernel time."""
    least_s = (batches * least_bytes_per_batch(nodes, pods, zones)
               / peaks(device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s
