"""The one keeper of built plans (`TPUScheduler._plans`, `ops/features.py`
`KeptPlan`; PR 46): a session's tail and the plan a preemptor derives from
are one entry holding one plan object, judged by one rule. What a session's
start and a preemptor's acquisition each make of an entry after every kind
of journal event is what the two holders of the parent commit made of it
(`self._resume`, `self._kept_plans`): the expected kinds, causes and
`kept` / `built` below were read off 4d6ecd4 with this file's scenario.
Counts of work only; nothing is timed."""

import pytest

from kubernetes_tpu.api.types import Namespace, Taint
from kubernetes_tpu.models import TPUScheduler, tpu_scheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def _node(name, labels=None, taints=()):
    b = make_node().name(name).capacity(
        {"cpu": "4", "memory": "32Gi", "pods": 110}).zone("zone-0")
    for k, v in (labels or {}).items():
        b = b.label(k, v)
    node = b.obj()
    node.taints = list(taints)
    return node


def _pod(name, cpu="100m", on=None, anti=False, gates=(), port=None):
    b = make_pod().name(name).uid(name).req({"cpu": cpu, "memory": "100Mi"})
    if anti:
        b = b.label("color", "green").pod_affinity(
            "kubernetes.io/hostname", {"color": "green"}, anti=True)
    for g in gates:
        b = b.scheduling_gate(g)
    if port:
        b = b.host_port(port)
    pod = b.obj()
    if on is not None:
        pod.node_name = on      # created bound: no fit is asked
    return pod


def _device(journal_cap=None, nodes=6):
    sched = TPUScheduler(max_batch=4)
    # sessions, not the hint walk, are what acquires plans here
    sched._hints.enabled = False
    sched._hints.entry = None
    if journal_cap is not None:
        sched.journal.cap = journal_cap     # a journal never shrinks
    for i in range(nodes):
        sched.clientset.create_node(_node(f"n{i}"))
    sched.run_until_idle()
    return sched, sched.clientset


_MADE = [0]


def _wave(sched, proto, n=3):
    """`n` clones of `proto`, scheduled to the end: one session. Returns
    (kind, cause) of its plan acquisition."""
    before = (sched.plan_rebuilds_full, sched.plan_rebuilds_delta,
              sched.plan_rebuilds_resume)
    for _ in range(n):
        _MADE[0] += 1
        pod = proto.clone_from_template(f"{proto.name}-{_MADE[0]}")
        pod.uid = pod.name
        sched.clientset.create_pod(pod)
    sched.run_until_idle()
    after = (sched.plan_rebuilds_full, sched.plan_rebuilds_delta,
             sched.plan_rebuilds_resume)
    moved = [k for k, a, b in zip(("full", "delta", "resume"), before, after)
             if b == a + 1]
    assert len(moved) == 1 and sum(after) == sum(before) + 1, (before, after)
    return moved[0], sched.plan_build_cause


def _ask(sched, pod, site="dry_run"):
    """How `_preemptor_plan` comes by a plan for `pod`, called as its two
    sites call it: snapshot and mirror brought up first."""
    sched._sync_mirror()
    fw = sched.framework_for_pod(pod)
    return sched._preemptor_plan(fw, pod, 1, site)[2]


def _tails(sched):
    return [e for e in sched._plans.values() if e.tail_seq is not None]


# -- one entry, one plan object, at most one tail ------------------------------

def test_a_sessions_tail_and_the_preemptors_plan_are_one_entry():
    sched, cs = _device()
    proto = _pod("a")
    assert _wave(sched, proto) == ("full", "first")
    (entry,) = sched._plans.values()
    assert entry.guard is not None and entry.tail_seq is not None
    plan = entry.plan
    # the preemptor derives from that very entry
    assert _ask(sched, proto) == "kept"
    assert list(sched._plans.values()) == [entry] and entry.plan is plan
    # and the session resumes from it: the tail is taken, and handed back
    # with the same plan at its end
    assert _wave(sched, proto) == ("resume", "")
    assert list(sched._plans.values()) == [entry] and entry.plan is plan
    assert entry.tail_seq is not None
    c = sched.metrics.preemptor_plans
    assert (c.value("dry_run", "kept"), c.value("dry_run", "built")) == (1, 0)


def test_a_second_templates_session_leaves_one_tail():
    sched, cs = _device()
    a, b = _pod("a"), _pod("b", cpu="200m")
    assert _wave(sched, a) == ("full", "first")
    assert _wave(sched, b) == ("full", "other_pod")
    assert len(sched._plans) == 2 and len(_tails(sched)) == 1
    (tail,) = _tails(sched)
    assert int(tail.plan.features.request[0]) == 200
    # a's plan is still there to derive from, without its tail
    assert _ask(sched, a) == "kept"
    for _ in range(3):
        assert _wave(sched, a)[0] == "full"
        assert _wave(sched, b)[0] == "full"
        assert len(sched._plans) == 2 and len(_tails(sched)) == 1


def test_a_plan_other_pods_can_move_is_kept_for_its_tail_alone():
    """A pod that `_resources_only_block` refuses: its entry is filed for its
    tail, never derived from, crowds nobody out, and leaves with its tail."""
    sched, cs = _device()
    ported = _pod("ported", port=8080)
    assert sched._resources_only_block(ported) is not None
    for i in range(tpu_scheduler._KEPT_PLANS):
        assert _ask(sched, _pod(f"t{i}", cpu=f"{300 + i}m")) == "built"
    assert len(sched._plans) == tpu_scheduler._KEPT_PLANS
    assert _wave(sched, ported, n=2) == ("full", "first")
    (tail,) = _tails(sched)
    assert tail.guard is None
    # it evicted nothing: all eight templates still derive
    assert len(sched._plans) == tpu_scheduler._KEPT_PLANS + 1
    for i in range(tpu_scheduler._KEPT_PLANS):
        assert _ask(sched, _pod(f"t{i}", cpu=f"{300 + i}m")) == "kept"
    # asked all the same, the keeper builds, and files nothing over the tail
    before = sched.metrics.preemptor_plans.value("nominated", "built")
    assert _ask(sched, ported, "nominated") == "built"
    assert _ask(sched, ported, "nominated") == "built"
    assert sched.metrics.preemptor_plans.value("nominated", "built") \
        == before + 2
    assert _tails(sched) == [tail]
    # while its own session resumes from the tail
    assert _wave(sched, ported, n=2) == ("resume", "")
    # another template's session start drops the tail, and the entry with it
    assert _wave(sched, _pod("t0", cpu="300m"))[0] == "full"
    assert len(sched._plans) == tpu_scheduler._KEPT_PLANS
    assert all(e.guard is not None for e in sched._plans.values())


# -- one rule: every kind of journal event -------------------------------------

def _a_gate_lifted(sched, cs):
    pod = cs.pods["gated"]
    pod.scheduling_gates = []
    cs.update_pod(pod)


def _a_namespace(sched, cs):
    cs.create_namespace(Namespace(name="late"))


def _a_plain_pod_created_bound(sched, cs):
    cs.create_pod(_pod("late", cpu="500m", on="n1"))


def _a_pod_with_terms_created_bound(sched, cs):
    cs.create_pod(_pod("picky", on="n1", anti=True))


def _a_taint_added(sched, cs):
    cs.update_node(_node("n3", taints=[
        Taint(key="dedicated", value="x", effect="NoSchedule")]))


def _a_nodes_labels_changed(sched, cs):
    cs.update_node(_node("n3", labels={"tier": "gold"}))


def _a_node_added(sched, cs):
    cs.create_node(_node("n-new"))


def _a_journal_overrun(sched, cs):
    assert sched.journal.cap == 8
    for i in range(5):
        cs.create_pod(_pod(f"filler-{i}", cpu="50m", on=f"n{i}"))
        cs.delete_pod(cs.pods[f"filler-{i}"])


# the event, what a preemptor's acquisition makes of the entry, and the kind
# and cause of the next session's: as at the parent commit
_EVENTS = (
    (_a_gate_lifted, "kept", ("delta", "")),
    (_a_namespace, "kept", ("delta", "")),
    (_a_plain_pod_created_bound, "kept", ("delta", "")),
    (_a_taint_added, "kept", ("delta", "")),
    (_a_pod_with_terms_created_bound, "built", ("full", "other_pod")),
    (_a_nodes_labels_changed, "built", ("full", "unpatchable")),
    (_a_node_added, "built", ("full", "structural")),
    (_a_journal_overrun, "built", ("full", "journal_overrun")),
)


def _after(event):
    """A scheduler whose one entry holds a tail and a plan to derive from,
    then `event`. Returns it with the template."""
    sched, cs = _device(journal_cap=8 if event is _a_journal_overrun else None)
    if event is _a_gate_lifted:
        cs.create_pod(_pod("gated", gates=("hold",)))
    proto = _pod("a")
    assert _wave(sched, proto) == ("full", "first")
    (entry,) = sched._plans.values()
    assert entry.guard is not None and entry.tail_seq is not None
    event(sched, cs)
    return sched, proto, entry


@pytest.mark.parametrize("first", ("session", "preemptor"))
@pytest.mark.parametrize("event,how,session", _EVENTS,
                         ids=[e[0].__name__.lstrip("_") for e in _EVENTS])
def test_the_tail_and_the_plan_outlive_an_event_together(event, how, session,
                                                         first):
    """Whichever of the two asks first, each gets what it got at the parent;
    and both keep the entry's one plan, or neither does."""
    sched, proto, entry = _after(event)
    if first == "preemptor":
        assert _ask(sched, proto) == how
        if how == "built" and session[1] != "other_pod":
            # the voided plan stays for its tail, which its own session's
            # start judges (and drops, with the parent's cause); nobody
            # derives from it meanwhile
            assert sched._plans and _tails(sched) == [entry]
            assert entry.guard is None
    assert _wave(sched, proto) == session
    kept = session[0] != "full"
    (now,) = [e for e in sched._plans.values() if e.tail_seq is not None]
    assert (now is entry and now.plan is entry.plan) == kept
    if first == "session":
        # a full build is kept in turn; either way the preemptor now
        # derives from the plan the session ran on
        assert _ask(sched, proto) == "kept"
    assert sched.device_breaker.consecutive_failures == 0
    assert sched.host_path_pods == 0


# -- a device failure ----------------------------------------------------------

# how each registered holder shows that it holds nothing
_EMPTY = {
    "mirror": lambda s: s.mirror._full_flush,
    "plans": lambda s: not s._plans,
    "victims": lambda s: s._victims._key is None and not s._victims._names,
    "hints": lambda s: s._hints.entry is None and not s._hints.entries,
    "placement_plans": lambda s: not s._placement_plan_cache,
    "placement_masks": lambda s: not s._placement_mask_cache,
    "fail_memo": lambda s: not s._fail_memo,
}


def test_a_device_failure_leaves_every_registered_holder_empty():
    sched = TPUScheduler(max_batch=4)
    cs = sched.clientset
    for i in range(4):
        cs.create_node(_node(f"n{i}"))
        for j in range(4):
            cs.create_pod(_pod(f"init-{i}-{j}", cpu="900m", on=f"n{i}"))
    # a holder registered later is walked here too: it needs its line above
    assert set(sched._device_holders) == set(_EMPTY)
    # fill them: a session (plan, tail, hint), a pod that fits nowhere (the
    # failure memo), a what-if (victims), and the placement caches by hand
    _wave(sched, _pod("a"))
    cs.create_pod(_pod("huge", cpu="64"))
    pre = make_pod().name("pre").uid("pre").req(
        {"cpu": "3", "memory": "100Mi"}).priority(10).obj()
    sched.run_until_idle()
    sched.device_dry_run_preemption(sched.framework_for_pod(pre), None, pre,
                                    {}, 4, 0)
    sched._placement_plan_cache["key"] = object()
    sched._placement_mask_cache["key"] = object()
    sched.mirror.flush()
    held = {name for name, empty in _EMPTY.items() if not empty(sched)}
    assert held == set(_EMPTY), set(_EMPTY) - held
    sched._note_device_failure(RuntimeError("injected"), "test")
    assert {name for name, empty in _EMPTY.items() if not empty(sched)} \
        == set()
    flushed = sched.metrics.batch_cache_flushed
    assert flushed.value("device_path_failure") == 1
