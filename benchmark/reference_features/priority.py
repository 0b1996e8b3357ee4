"""The pod's priority (``spec.priority``, the value a PriorityClass resolves
to), and DefaultPreemption, the PostFilter plugin that reads it.

Template value: an integer (32 bits, as the API's). A template without the
key has priority 0, the default, and never preempts here (it is "treated as
today": it pends). Filter and scores never read the priority, so a pod with a
priority that finds a node is placed as any other. What priority does:

- the queue pops the higher priority first (PrioritySort): the reference
  schedules in the order of the run's log, so the driver lists a pod where
  the scheduler attempted it;
- a pod that finds no node goes to PostFilter (``State.post_filter``, called
  by the core for a pod whose group may pend), which is kube-scheduler's
  ``framework/preemption/preemption.go`` (``Evaluator.Preempt`` :181,
  ``findCandidates`` :201, ``SelectCandidate`` / ``pickOneNodeForPreemption``
  :286, ``DryRunPreemption`` :425) with
  ``plugins/defaultpreemption/default_preemption.go``
  (``PodEligibleToPreemptOthers``, ``GetOffsetAndNumCandidates``,
  ``SelectVictimsOnNode``), rule by rule:

  1. eligible (``PodEligibleToPreemptOthers``): unless the pod holds a
     nomination and a pod of lower priority is still leaving its nominated
     node (evicted, its ``delete`` not in the log yet): preemption is under
     way there, and the attempt ends with nothing done;
  2. candidates (``findCandidates``, ``DryRunPreemption``): the nodes in walk
     order (the node tree's list, as the cycle walks it) from the offset,
     until ``max(n * 10 // 100, 100)`` of them, at most ``n``, are candidates
     (``minCandidateNodesPercentage`` 10, ``minCandidateNodesAbsolute`` 100,
     the defaults). DEPARTURE, the offset: the source draws it at random for
     every attempt (``rand.Int31n(numNodes)``) and dry-runs the nodes on 16
     goroutines, so which nodes are tried and in which order they are found
     is left open there; any fixed rule is within it. The rule here: the
     offset is the number of PostFilters of this run that got as far as the
     search before this one (``ref.candidate_searches``), modulo ``n``; the
     candidates are kept in walk order. The reference counts that itself and
     takes no offset from the run. (The source numbers only the nodes whose
     filter status is not UnschedulableAndUnresolvable; every refusal the
     modelled filters give for lack of room, skew or an anti-affinity term
     is resolvable, and a node that a required affinity refuses gives no
     candidate here either, so all nodes are numbered);
  3. victims on a node (``SelectVictimsOnNode``): every pod of LOWER priority
     leaves the node; if there is none, or the preemptor then does not pass
     the filters there (cpu, memory, pod count and every active feature's
     filter, with the nominated pods that hold room against it counted:
     ``RunFilterPluginsWithNominatedPods``), the node is no candidate; else
     the pods are put back one by one, the most important first
     (``MoreImportantPod``: higher priority, then earlier start), and each
     with which the preemptor no longer passes is taken off again and is a
     victim. DEPARTURE, start times: scheduler_perf runs no kubelet, no pod
     has a ``status.startTime``, and the source then reads "now" at every
     comparison, which orders nothing. The start time here is the pod's
     place among the log's creates, the order in which the scheduler
     admitted the pods. There are no PodDisruptionBudgets in any
     configuration: every victim is "non-violating", and the first criterion
     of rule 4 in the source (fewest PDB violations) is always a tie;
  4. the node (``pickOneNodeForPreemption``), each criterion over the
     candidates the one before left tied: the lowest priority of a node's
     most important victim; the smallest sum over its victims of ``priority +
     2**31`` (the source's ``MaxInt32 + 1``, which makes every term positive:
     fewer victims beat a smaller plain sum); the fewest victims; the LATEST
     earliest start among each node's victims of its highest priority
     (``GetEarliestPodStartTime``); then the first candidate in the order
     found (the source takes the first of a Go map's iteration, which is
     open);
  5. what follows (``prepareCandidate``): ``ref.nominate``: the victims are
     recorded as evicted and leave where the log deletes them, the preemptor
     holds its room on the node (``holds_room``: against every pod of equal
     or lower priority, ``addGENominatedPods``), and the pods of lower
     priority that held room on that node lose it. A pod that finds no
     candidate pends and nothing else changes;

- the retry is the core's (``Reference.retry``), where the log has it.

Features beside it. A victim that leaves, and a nominated pod that is added,
goes through every active feature's ``account``, which is how the source's
``RunPreFilterExtensionRemovePod`` / ``AddPod`` keep spread counts and
affinity tables: exact for ``topologySpreadConstraints``, ``podAntiAffinity``
and ``podAffinity`` as they are modelled, since their states are sums over
placed pods. Tested by hand: the resource fit alone and with
``topologySpreadConstraints`` (tests/benchmark/test_benchmark_preemption.py);
with the two affinity features it is untested.

Refused as ``Unmodelled``: anything but a 32-bit integer. Not modelled, and
absent from every configuration: PodDisruptionBudgets,
``preemptionPolicy: Never`` (no template key carries it), extenders'
preempt verb, pod-group preemption.

Controls (``control.py`` style, ``priority.<name>``), each one rule above
broken: ``victims_evicted`` (the candidate check left out: a preemptor that
fits no node has every lower pod evicted everywhere), ``no_reprieve`` (rule
3's second half: every lower pod of the node is a victim),
``first_candidate`` (rule 4 skipped), ``room_not_held`` (rule 5's held room
counts against nobody), ``bound_at_first_attempt`` (the preemptor lands on
its node at once, the victims still there, and the retry finds it bound),
``offset_never_advanced`` (rule 2's offset stays 0).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from reference import Unmodelled

KEY = "priority"
MIN_CANDIDATE_NODES_PERCENTAGE = 10
MIN_CANDIDATE_NODES_ABSOLUTE = 100
PRIORITY_OFFSET = 1 << 31           # MaxInt32 + 1


def parse(value, template: dict) -> int:
    if (isinstance(value, bool) or not isinstance(value, int)
            or not -(1 << 31) <= value < 1 << 31):
        raise Unmodelled(f"{KEY} {value!r}: a 32-bit integer")
    return value


def of(pod) -> int:
    return pod.features.get(KEY, 0)


class State:
    """DefaultPreemption over the reference's rows. Per node, what the pods
    of each priority hold, so that "would fit once every pod below it had
    left" is three comparisons a node and the dry run visits only those."""

    filters_nothing = True          # `feasible` never has a say

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

    def feasible(self, pod):
        return None

    def score(self, pod, rows):
        return None

    def holds_room(self, nominated, pod) -> bool:
        return of(nominated) >= of(pod)

    # -- PostFilter --------------------------------------------------------

    def fits_once_lower_left(self, pod, name=None) -> np.ndarray:
        """By cpu, memory and pod count alone: the rows that would take the
        pod once every pod of lower priority had left them, the room held
        against it counted."""
        ref = self.ref
        kept = [np.zeros(ref.n, np.int64) for _ in range(3)]
        for priority, held in self.held.items():
            if priority >= of(pod):
                for total, part in zip(kept, held):
                    total += part
        nominated = ref._held_against(pod, name) if ref.nominated else None
        if nominated is not None:
            for total, part in zip(kept, nominated[:3]):    # cpu, memory, pods
                total += part
        return ((kept[2] + 1 <= ref.alloc_pods)
                & (pod.cpu <= ref.alloc_cpu - kept[0])
                & (pod.memory <= ref.alloc_mem - kept[1]))

    def eligible(self, name, pod) -> bool:
        ref = self.ref
        held = ref.nominated.get(name)
        if held is None:
            return True
        return not any(
            ref.placed[victim][0] == held[0]
            and of(ref.placed[victim][1]) < of(pod)
            for victim in ref.terminating if victim in ref.placed)

    def offset(self) -> int:
        ref = self.ref
        ref.candidate_searches += 1
        return (ref.candidate_searches - 1) % ref.n

    def victims_on(self, row, pod, name, there) -> Optional[list]:
        """``there``: the pods on ``row`` as (born, name, pod). The victims
        as such triples, most important first; None: no candidate."""
        ref = self.ref
        lower = sorted((t for t in there if of(t[2]) < of(pod)),
                       key=lambda t: (-of(t[2]), t[0]))
        if not lower:
            return None
        for _, _, victim in lower:
            ref._account(row, victim, -1)
        victims, off = None, lower
        if ref.feasible_on(pod, row, name):
            victims = off = []
            for t in lower:
                ref._account(row, t[2], +1)
                if not self.reprieved(pod, row, name):
                    ref._account(row, t[2], -1)
                    victims.append(t)
        for _, _, victim in off:        # the dry run leaves no trace
            ref._account(row, victim, +1)
        return victims

    def reprieved(self, pod, row, name) -> bool:
        return self.ref.feasible_on(pod, row, name)

    def candidates(self, name, pod, start) -> List[Tuple[int, list]]:
        ref = self.ref
        order = (np.arange(ref.n) + start) % ref.n
        rows = order[self.fits_once_lower_left(pod, name)[order]]
        want = min(max(ref.n * MIN_CANDIDATE_NODES_PERCENTAGE // 100,
                       MIN_CANDIDATE_NODES_ABSOLUTE), ref.n)
        wanted = set(rows.tolist())
        there = {}
        for victim, (row, shape) in ref.placed.items():
            if row in wanted:
                there.setdefault(row, []).append(
                    (ref.born[victim], victim, shape))
        found = []
        for row in rows.tolist():
            victims = self.victims_on(row, pod, name, there.get(row, ()))
            if victims:
                found.append((row, victims))
                if len(found) >= want:
                    break
        return found

    @staticmethod
    def pick(candidates):
        def key(i):
            victims = candidates[i][1]
            highest = of(victims[0][2])
            return (highest,
                    sum(of(v) + PRIORITY_OFFSET for _, _, v in victims),
                    len(victims),
                    -min(born for born, _, v in victims if of(v) == highest),
                    i)
        return candidates[min(range(len(candidates)), key=key)]

    def post_filter(self, name, pod) -> None:
        ref = self.ref
        if KEY not in pod.features or not ref.n \
                or not self.eligible(name, pod):
            return
        found = self.candidates(name, pod, self.offset())
        if not found:
            return
        row, victims = self.pick(found)
        ref.nominate(
            name, row, [victim for _, victim, _ in victims],
            cleared=[other for other, (at, held) in ref.nominated.items()
                     if at == row and other != name and of(held) < of(pod)])


class VictimsEvicted(State):
    """The candidate check left out: a preemptor that fits nowhere still has
    every lower-priority pod evicted, everywhere. The evicted pods keep the
    node the run bound them to and count nowhere from then on."""

    def post_filter(self, name, pod):
        ref = self.ref
        if KEY in pod.features:
            for victim, (row, shape) in list(ref.placed.items()):
                if of(shape) < of(pod):
                    del ref.placed[victim]
                    ref._gone[victim] = ref.names[row]
                    ref._account(row, shape, -1)


class NoReprieve(State):
    """Every pod of lower priority on the chosen node is evicted, whether or
    not the preemptor needs its room."""

    def reprieved(self, pod, row, name):
        return False


class FirstCandidate(State):
    """The first candidate found is taken, whatever its victims."""

    @staticmethod
    def pick(candidates):
        return candidates[0]


class RoomNotHeld(State):
    """A nominated pod's room is held against nobody: the pods that follow
    take it."""

    def holds_room(self, nominated, pod):
        return False


class BoundAtFirstAttempt(State):
    """The preemptor is bound to the chosen node in the attempt that evicts,
    with the victims still on it; the log's retry then finds no pending pod
    and the replay ends there."""

    def post_filter(self, name, pod):
        super().post_filter(name, pod)
        held = self.ref.nominated.get(name)
        if held is not None:
            self.ref._land(name, pod, held[0])


class OffsetNeverAdvanced(State):
    """Every candidate search starts at the list's first node."""

    def offset(self):
        return 0


CONTROLS = {"victims_evicted": VictimsEvicted, "no_reprieve": NoReprieve,
            "first_candidate": FirstCandidate, "room_not_held": RoomNotHeld,
            "bound_at_first_attempt": BoundAtFirstAttempt,
            "offset_never_advanced": OffsetNeverAdvanced}
