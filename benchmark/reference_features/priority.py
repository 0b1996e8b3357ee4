"""The pod's priority (``spec.priority``, the value a PriorityClass resolves
to).

Template value: an integer. A template without the key has priority 0, the
default. In the reference scheduler priority does three things, and this file
models the one case in which none of them can move a placement:

- the queue pops the higher priority first (PrioritySort): the reference
  schedules in the order of the run's log, so the driver lists a pod where the
  scheduler attempted it (``drivers/waves_churn.py`` creates a churn pod only
  while no other pod waits);
- a pod that finds no node goes to PostFilter, and DefaultPreemption evicts
  pods of LOWER priority from a node on which the pod would then fit
  (``preemption.go`` SelectVictimsOnNode: remove every lower-priority pod,
  check the filters, reprieve what can stay). Preemption is NOT modelled:
  ``State.feasible`` refuses (``Unmodelled``) a pod that resources refuse on
  every node now and that would fit some node once every pod of lower priority
  had left it. What is left is the pod that can evict nothing that would admit
  it: it stays pending and nothing else changes;
- a pod nominated to a node reserves its room against pods of lower or equal
  priority: there is no nomination without a candidate, so none here.

Filter and scores never read the priority, so a pod with a priority that does
find a node is placed as any other.

Refused as ``Unmodelled``: anything but an integer; a pod that preemption
could admit (above).

Controls (``control.py`` style, ``priority.<name>``): ``victims_evicted``: a
dry run that takes every node for a candidate although the preemptor fits
none: the pods of lower priority are evicted everywhere, and the pods that
follow meet a cluster that looks empty.
"""

from __future__ import annotations

import numpy as np

from reference import Unmodelled

KEY = "priority"


def parse(value, template: dict) -> int:
    if isinstance(value, bool) or not isinstance(value, int):
        raise Unmodelled(f"{KEY} {value!r}: an integer")
    return value


def of(pod) -> int:
    return pod.features.get(KEY, 0)


class State:
    """Per node, what the pods of each priority hold, so that a pod's fit
    "once every pod below it has left" is three comparisons a node."""

    def __init__(self, ref):
        self.ref = ref
        self.held = {}        # priority -> (cpu[n], memory[n], pods[n])

    def account(self, row, pod, sign):
        held = self.held.get(of(pod))
        if held is None:
            held = self.held[of(pod)] = tuple(
                np.zeros(self.ref.n, np.int64) for _ in range(3))
        held[0][row] += sign * pod.cpu
        held[1][row] += sign * pod.memory
        held[2][row] += sign

    def fits_once_lower_left(self, pod) -> np.ndarray:
        ref = self.ref
        kept = [np.zeros(ref.n, np.int64) for _ in range(3)]
        for priority, held in self.held.items():
            if priority >= of(pod):
                for total, part in zip(kept, held):
                    total += part
        return ((kept[2] + 1 <= ref.alloc_pods)
                & (pod.cpu <= ref.alloc_cpu - kept[0])
                & (pod.memory <= ref.alloc_mem - kept[1]))

    def fits_now(self, pod) -> np.ndarray:
        ref = self.ref
        return ((ref.n_pods + 1 <= ref.alloc_pods)
                & (pod.cpu <= ref.alloc_cpu - ref.req_cpu)
                & (pod.memory <= ref.alloc_mem - ref.req_mem))

    def feasible(self, pod):
        if KEY in pod.features and not self.fits_now(pod).any() \
                and self.fits_once_lower_left(pod).any():
            raise Unmodelled(
                f"a pod of priority {of(pod)} fits no node now and would fit "
                f"one once the pods of lower priority had left it: "
                f"preemption is not modelled")
        return None

    def score(self, pod, rows):
        return None


class VictimsEvicted(State):
    """The candidate check left out: a preemptor that fits nowhere still has
    every lower-priority pod evicted, everywhere. The evicted pods keep the
    node the run bound them to and count nowhere from then on."""

    def feasible(self, pod):
        ref = self.ref
        if KEY in pod.features and not self.fits_now(pod).any():
            for name, (row, victim) in list(ref.placed.items()):
                if of(victim) < of(pod):
                    del ref.placed[name]
                    ref._gone[name] = ref.names[row]
                    ref._account(row, victim, -1)
        return None


CONTROLS = {"victims_evicted": VictimsEvicted}
