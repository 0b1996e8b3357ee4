"""The least bytes one batch of the normalising scan must move, from shapes
alone: `kernelcost.least_bytes_per_batch` for a kernel that also scores every
node by the pods on it (InterPodAffinity's preferred terms) and normalises
that score over the kept rows at every step.

A pod with preferred pod-affinity terms is placed against the node state of
`kernelcost.py` (its fit lanes: the per-node quantities the resource filter
and the two resource scores read once and write back once, the pod batch
read, one result per pod written; no zone lane), and besides

    read  per node : the hostname's value index (int32) and the base of the
                     raw score, what the pods already there pull
                     (`ipa_base`, int64)
    per landing axis: its row of deltas, one a hostname value and so one a
                     node, read once and written once (a landing raises its
                     own node's raw score for the pods after it) (2 x int64)

A floor on traffic, not what the kernel moves (the scan passes over the node
rows at every step: two reductions and a bounded division over all of them
for each pod of the batch): the share of the roofline it yields says how far
the normalising scan is from being memory-bound. It cannot pass 100 % while
the kernel reads each of these once.
"""

from __future__ import annotations

import kernelcost

I32, I64 = kernelcost.I32, kernelcost.I64
PREFERRED = "preferredDuringSchedulingIgnoredDuringExecution"


def ipa_least_bytes_per_batch(nodes: int, pods: float, axes: int) -> float:
    fit = kernelcost.least_bytes_per_batch(nodes, pods, zones=0)
    return fit + nodes * (I32 + I64) + axes * nodes * 2 * I64


def ipa_hbm_roofline_share(kernel_s: float, batches: int, nodes: int,
                           pods: float, axes: int, device_kind: str) -> float:
    """Percent: least time at peak HBM bandwidth over measured kernel time."""
    least_s = (batches * ipa_least_bytes_per_batch(nodes, pods, axes)
               / kernelcost.peaks(device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s


def landing_axes(template: dict) -> int:
    """How many topology keys a landed pod of `template` changes the raw
    score along: the keys of its preferred pod-affinity terms that select the
    pod itself (identical pods pull each other, both ways)."""
    labels = template.get("labels", {})
    own = template.get("namespace", "default")
    keys = set()
    for wt in (template.get("podAffinity") or {}).get(PREFERRED, ()):
        term = wt.get("podAffinityTerm") or {}
        wanted = (term.get("labelSelector") or {}).get("matchLabels") or {}
        if (own in (term.get("namespaces") or (own,)) and wanted
                and all(labels.get(k) == v for k, v in wanted.items())):
            keys.add(term.get("topologyKey"))
    return len(keys)
