"""The least bytes one batch with an anti-affinity lane must move, from
shapes alone: `kernelcost.least_bytes_per_batch` for a kernel that also
refuses single nodes by the pods on them.

A pod with required anti-affinity terms over the hostname is placed against
the node state of `kernelcost.py` (its fit lanes: the per-node quantities the
resource filter and the two resource scores read once and write back once,
the pod batch read, one result per pod written; no zone lane), and besides

    read  per node: the hostname's value index, and the count of existing
                    pods whose own terms refuse the incoming pod
                    (`exist_anti`)                              (2 x int32)
    per term      : its row of matching-pod counts, one count a hostname
                    value and so one a node, read once and written once
                    (a landing raises its own node's count)     (2 x int32)

A floor on traffic, not what the kernel moves (the lap kernel passes over the
node tensors once a lap, some 150 laps a 1,024-pod batch at 5,000 nodes): the
share of the roofline it yields says how far the lap path is from being
memory-bound.
"""

from __future__ import annotations

import json
import os
from typing import Optional

import kernelcost
import progspans

I32 = kernelcost.I32
REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
HERE = os.path.dirname(os.path.abspath(__file__))


def anti_least_bytes_per_batch(nodes: int, pods: int, terms: int) -> int:
    fit = kernelcost.least_bytes_per_batch(nodes, pods, zones=0)
    return fit + nodes * 2 * I32 + terms * nodes * 2 * I32


def anti_hbm_roofline_share(kernel_s: float, batches: int, nodes: int,
                            pods: float, terms: int, device_kind: str
                            ) -> float:
    """Percent: least time at peak HBM bandwidth over measured kernel time."""
    least_s = (batches * anti_least_bytes_per_batch(nodes, pods, terms)
               / kernelcost.peaks(device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s


def required_anti_terms(template: dict) -> int:
    """How many required anti-affinity terms a pod template carries."""
    return len((template.get("podAntiAffinity") or {}).get(REQUIRED, ()))


def measured_template() -> Optional[dict]:
    """The measured pods' template of the cell this process runs: readers are
    handed what the run observed and not its configuration, so the cell is
    found as `progspans` finds the run's directory, from `run.py`'s own
    arguments (`--workload`, and `--manifest` / `--bench-dir` where given).
    None where they name no cell."""
    workload = progspans._argument("--workload")
    manifest = progspans._argument("--manifest") or os.path.join(
        progspans.ROOT, "BENCHMARK.json")
    bench_dir = progspans._argument("--bench-dir") or HERE
    try:
        with open(manifest) as f:
            cells = {w["name"]: w for w in json.load(f)["workloads"]}
        with open(os.path.join(bench_dir, "configs",
                               cells[workload]["config"] + ".json")) as f:
            return json.load(f)["measurePods"]["template"]
    except (OSError, KeyError):
        return None
