"""A preemptor's plan kept per template (`TPUScheduler._preemptor_plan`,
`ops/features.py` `KeptPlan`, PR 45): the preemption what-if and a nominated
pod's evaluation of its own node take the template's kept plan and derive
again the nominated lane, the row mask, the start index and the result width.
Whatever happened since the plan was built, what they get equals what a fresh
`build_plan` gives, every `BatchFeatures` field alike in shape, dtype, value
and placement and every static attribute of `BatchPlan` equal; an event that
`_classify_delta` does not let a plan outlive, or a change of what sizes its
arrays, drops the entry and the site builds; and a batch of preemptors ends
as the host scheduler ends it, with every acquisition but the first `kept`.
No timing is asserted."""

import dataclasses

import numpy as np
import pytest

from kubernetes_tpu.api.types import Taint
from kubernetes_tpu.core import Scheduler
from kubernetes_tpu.core.node_info import PodInfo
from kubernetes_tpu.testing.annotations import StageAnnotations
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def _node(name, cpu="4", labels=None, taints=()):
    b = make_node().name(name).capacity(
        {"cpu": cpu, "memory": "32Gi", "pods": 110}).zone("zone-0")
    for k, v in (labels or {}).items():
        b = b.label(k, v)
    node = b.obj()
    node.taints = list(taints)
    return node


_ORDINAL = [0]


def _pod(name, cpu="100m", priority=0, on=None, anti=False, **more):
    b = (make_pod().name(name).uid(name)
         .req({"cpu": cpu, "memory": "100Mi", **more}).priority(priority))
    if anti:
        b = b.label("color", "green").pod_affinity(
            "kubernetes.io/hostname", {"color": "green"}, anti=True)
    pod = b.obj()
    _ORDINAL[0] += 1
    pod.creation_ts = float(_ORDINAL[0])
    if on is not None:
        pod.node_name = on      # created bound: no fit is asked
    return pod


def _device(nodes=6, journal_cap=None):
    """A `TPUScheduler` over `nodes` nodes of 4 cpu, each kept full by four
    pods of 900m at a low priority (the shape of `preempt-5k`)."""
    from kubernetes_tpu.models import TPUScheduler
    sched = TPUScheduler()
    if journal_cap is not None:
        sched.journal.cap = journal_cap     # a journal never shrinks
    cs = sched.clientset
    for i in range(nodes):
        cs.create_node(_node(f"n{i}"))
    for i in range(nodes):
        for j in range(4):
            cs.create_pod(_pod(f"init-{i}-{j}", cpu="900m", priority=-10,
                               on=f"n{i}"))
    sched.run_until_idle()
    return sched, cs


def _same_plan(got, want):
    for name in got.features._fields:
        a, b = getattr(got.features, name), getattr(want.features, name)
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim), name
    for f in dataclasses.fields(got):
        if f.name != "features":
            assert getattr(got, f.name) == getattr(want, f.name), f.name


def _ask(sched, fw, pre, batch, site, only_row=None):
    """`_preemptor_plan` as its two sites call it: snapshot and mirror
    brought up first (`_sync_mirror`)."""
    sched._sync_mirror()
    return sched._preemptor_plan(fw, pre, batch, site, only_row=only_row)


def _acquire(sched, pre, site="dry_run", only_row=None):
    """The keeper's plan for `pre` beside a fresh `build_plan`'s, held equal;
    returns how the keeper came by it."""
    fw = sched.framework_for_pod(pre)
    batch = 1 if site == "dry_run" else sched.max_batch
    state, got, how = _ask(sched, fw, pre, batch, site, only_row)
    want_state, want = sched.build_plan(fw, pre, batch, only_row=only_row)
    _same_plan(got, want)
    # the device state is the mirror's flush on both sides
    for a, b in zip(state, want_state):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    return how


def _plans(sched):
    c = sched.metrics.preemptor_plans
    return {k: int(c.value(*k)) for k in (
        ("dry_run", "kept"), ("dry_run", "built"),
        ("nominated", "kept"), ("nominated", "built")) if c.value(*k)}


def _nominate(sched, name, node, priority=10, cpu="3", **more):
    pod = _pod(name, cpu=cpu, priority=priority, **more)
    pod.nominated_node_name = node
    sched.queue.nominator.add_nominated_pod(PodInfo.of(pod), node)
    return pod


# -- (1) what a kept plan outlives ---------------------------------------------

def _three_plain_pods_evicted(sched, cs, pre):
    for j in range(3):
        cs.delete_pod(cs.pods[f"init-2-{j}"])


def _a_plain_pod_created_bound(sched, cs, pre):
    cs.delete_pod(cs.pods["init-1-0"])
    cs.create_pod(_pod("late", cpu="500m", on="n1"))


def _a_plain_pod_bound_by_this_scheduler(sched, cs, pre):
    cs.delete_pod(cs.pods["init-4-3"])
    cs.create_pod(_pod("placed", cpu="700m"))
    sched.run_until_idle()
    assert cs.pods["placed"].node_name == "n4"


def _a_nomination_added(sched, cs, pre):
    _nominate(sched, "other", "n3")


def _a_nomination_cleared(sched, cs, pre):
    other = _nominate(sched, "other", "n3")
    assert _acquire(sched, pre) == "kept"
    sched.queue.nominator.delete_nominated_pod(other)


def _a_nomination_of_lower_priority(sched, cs, pre):
    _nominate(sched, "meek", "n3", priority=pre.priority - 1)


def _a_nomination_with_a_scalar_the_mirror_knows(sched, cs, pre):
    # the slot was interned by the node's own allocatable: no width grows
    node = cs.nodes["n5"]
    node.allocatable.scalar_resources["example.com/known"] = 4
    cs.update_node(node)
    assert _acquire(sched, pre) == "kept"
    _nominate(sched, "asks", "n5", **{"example.com/known": 1})


def _taint(effect):
    def change(sched, cs, pre):
        old = cs.nodes["n3"]
        cs.update_node(_node("n3", taints=[
            Taint(key="dedicated", value="x", effect=effect)]))
        assert old.labels == cs.nodes["n3"].labels
    change.__name__ = f"_a_{effect}_taint_added"
    return change


_OUTLIVED = (_three_plain_pods_evicted, _a_plain_pod_created_bound,
             _a_plain_pod_bound_by_this_scheduler, _a_nomination_added,
             _a_nomination_cleared, _a_nomination_of_lower_priority,
             _a_nomination_with_a_scalar_the_mirror_knows,
             _taint("PreferNoSchedule"), _taint("NoSchedule"))


@pytest.mark.parametrize("site", ("dry_run", "nominated"))
@pytest.mark.parametrize("change", _OUTLIVED,
                         ids=lambda f: f.__name__.lstrip("_"))
def test_the_kept_plan_equals_a_fresh_build_after(change, site):
    sched, cs = _device()
    pre = _pod("pre", cpu="3", priority=10)
    only_row = 2 if site == "nominated" else None
    assert _acquire(sched, pre, site, only_row) == "built"
    assert _acquire(sched, pre, site, only_row) == "kept"  # nothing happened
    before = _plans(sched)
    change(sched, cs, pre)
    assert _acquire(sched, pre, site, only_row) == "kept"
    after = _plans(sched)
    assert after[(site, "kept")] > before[(site, "kept")]
    assert after[(site, "built")] == 1
    # the other site finds the same entry, with and without the row mask
    other = "nominated" if site == "dry_run" else "dry_run"
    assert _acquire(sched, pre, other, 4 if site == "dry_run" else None) \
        == "kept"
    assert sched.device_breaker.consecutive_failures == 0


def test_a_taint_that_comes_and_goes_moves_has_pns_both_ways():
    sched, cs = _device()
    pre = _pod("pre", cpu="3", priority=10)
    fw = sched.framework_for_pod(pre)
    assert _acquire(sched, pre) == "built"
    _taint("PreferNoSchedule")(sched, cs, pre)
    assert _acquire(sched, pre) == "kept"
    assert _ask(sched, fw, pre, 1, "dry_run")[1].has_pns
    cs.update_node(_node("n3"))
    assert _acquire(sched, pre) == "kept"
    assert not _ask(sched, fw, pre, 1, "dry_run")[1].has_pns


def test_a_one_row_plan_keeps_the_rows_own_verdict_and_refuses_the_padding():
    """A plan over one row (a nominated pod's own node) holds that node's
    row as the kept plan has it, in row 0 of arrays of the smallest tier,
    with a state of the same rows; the padding is refused."""
    sched, cs = _device()
    pre = _pod("pre", cpu="3", priority=10)
    fw = sched.framework_for_pod(pre)
    assert _acquire(sched, pre, "nominated", 5) == "built"
    for row in (0, 3, 5):
        assert _acquire(sched, pre, "nominated", row) == "kept"
        state, plan, _how = _ask(sched, fw, pre, sched.max_batch,
                                 "nominated", row)
        assert plan.rows == (row,)
        assert plan.narrowed_attrs() == {"narrowed_rows": 1, "plan_rows": 64}
        f = plan.features
        assert (int(f.num_nodes), int(f.to_find), int(f.start_index)) \
            == (1, 1, 0)
        ok = np.asarray(f.extra_ok)
        assert ok.shape == (64,) and ok[0] and not ok[1:].any()
        valid = np.asarray(state.valid)
        assert valid[0] and not valid[1:].any()
        assert int(np.asarray(state.name_id)[0]) == sched.mirror.h_name_id[row]
        assert np.array_equal(np.asarray(state.req_r)[0],
                              sched.mirror.h_req_r[row])


# -- (2) what drops it ---------------------------------------------------------

def _a_node_added(sched, cs, pre):
    cs.create_node(_node("n-new"))


def _a_node_deleted(sched, cs, pre):
    cs.delete_node("n4")


def _a_nodes_labels_changed(sched, cs, pre):
    cs.update_node(_node("n3", labels={"tier": "gold"}))


def _an_anti_affinity_pod_created_bound(sched, cs, pre):
    cs.delete_pod(cs.pods["init-1-0"])
    cs.create_pod(_pod("picky", cpu="100m", on="n1", anti=True))


def _an_anti_affinity_pod_bound_by_this_scheduler(sched, cs, pre):
    # this scheduler's own bind is not journalled: the guard sees it
    cs.delete_pod(cs.pods["init-1-0"])
    cs.create_pod(_pod("picky", cpu="100m", anti=True))
    sched.run_until_idle()
    assert cs.pods["picky"].node_name == "n1"


def _r_slots_grown(sched, cs, pre):
    # five never-seen scalar resources on a bound pod: past the mirror's four
    cs.create_pod(_pod("odd", priority=-10, on="n1", **{
        f"example.com/thing-{i}": 1 for i in range(5)}))


def _r_slots_grown_by_a_nomination(sched, cs, pre):
    _nominate(sched, "asks", "n5", **{
        f"example.com/thing-{i}": 1 for i in range(5)})


def _a_journal_overrun(sched, cs, pre):
    assert sched.journal.cap == 8
    for i in range(5):
        for j in range(2):
            cs.delete_pod(cs.pods[f"init-{i}-{j}"])


_DROPS = (_a_node_added, _a_node_deleted, _a_nodes_labels_changed,
          _an_anti_affinity_pod_created_bound,
          _an_anti_affinity_pod_bound_by_this_scheduler, _r_slots_grown,
          _r_slots_grown_by_a_nomination, _a_journal_overrun)


@pytest.mark.parametrize("site", ("dry_run", "nominated"))
@pytest.mark.parametrize("change", _DROPS,
                         ids=lambda f: f.__name__.lstrip("_"))
def test_the_entry_is_dropped_and_the_site_builds_after(change, site):
    sched, cs = _device(
        journal_cap=8 if change is _a_journal_overrun else None)
    pre = _pod("pre", cpu="3", priority=10)
    only_row = 2 if site == "nominated" else None
    assert _acquire(sched, pre, site, only_row) == "built"
    assert _acquire(sched, pre, site, only_row) == "kept"
    change(sched, cs, pre)
    assert _acquire(sched, pre, site, only_row) == "built"
    assert _plans(sched) == {(site, "kept"): 1, (site, "built"): 2}
    # and the build is kept in turn
    assert _acquire(sched, pre, site, only_row) == "kept"
    assert sched.device_breaker.consecutive_failures == 0


@pytest.mark.parametrize("only_row", (None, 3))
def test_a_pod_whose_plan_other_pods_can_move_is_built_every_time(only_row):
    """Neither site sends such a pod (`_resources_only_block` stands before
    both); asked all the same, it gets a fresh build's plan and no entry."""
    sched, cs = _device()
    pre = make_pod().name("ported").uid("ported").req(
        {"cpu": "3", "memory": "100Mi"}).priority(10).host_port(8080).obj()
    for _ in range(2):
        assert _acquire(sched, pre, "nominated", only_row) == "built"
    assert not sched._plans
    assert _plans(sched) == {("nominated", "built"): 2}


def test_the_dry_run_ends_alike_whether_the_plan_was_kept_or_built():
    """The what-if's candidates with the keeper emptied before each call
    against the candidates with it left alone, nominations present."""
    answers = []
    for emptied in (False, True):
        sched, cs = _device()
        fw = None
        seen = []
        for step in range(3):
            pre = _pod(f"pre-{step}", cpu="3", priority=10)
            fw = fw or sched.framework_for_pod(pre)
            if emptied:
                sched._plans.clear()
            found = sched.device_dry_run_preemption(fw, None, pre, {}, 6, 0)
            seen.append([(c.node_name, [v.pod.name for v in c.victims])
                         for c in found])
            for j in range(3):
                cs.delete_pod(cs.pods[f"init-{step}-{j}"])
            _nominate(sched, f"held-{step}", f"n{step}")
        answers.append(seen)
        c = sched.metrics.preemptor_plans
        assert c.value("dry_run", "built") == (3 if emptied else 1)
        assert c.value("dry_run", "kept") == (0 if emptied else 2)
    assert answers[0] == answers[1] and all(answers[0])


# -- (3) a batch of preemptors, end to end -------------------------------------

def _preempt_run(device, templates):
    """Eight full nodes, then preemptors of `templates` (name stem, milli
    cpu) taking turns, six in all, scheduled to the end. Returns the
    scheduler, its recorder and what happened."""
    if device:
        sched, cs = _device(8)
    else:
        sched = Scheduler(deterministic_ties=True)
        cs = sched.clientset
        for i in range(8):
            cs.create_node(_node(f"n{i}"))
        for i in range(8):
            for j in range(4):
                cs.create_pod(_pod(f"init-{i}-{j}", cpu="900m", priority=-10,
                                   on=f"n{i}"))
        sched.run_until_idle()
    rec = sched.stages._annotation = StageAnnotations()
    protos = {stem: _pod(stem, cpu=f"{milli}m", priority=10)
              for stem, milli in templates}
    for i in range(6):
        stem, _cpu = templates[i % len(templates)]
        pod = protos[stem].clone_from_template(f"{stem}-{i}")
        pod.uid = pod.name
        _ORDINAL[0] += 1
        pod.creation_ts = float(_ORDINAL[0])
        cs.create_pod(pod)
    nominated = {}
    for _ in range(400):
        if not sched.schedule_one():
            break
        for p in cs.pods.values():
            if p.nominated_node_name:
                nominated.setdefault(p.name, p.nominated_node_name)
    sched.run_until_idle()
    return sched, rec, {
        "bindings": {p.name: p.node_name for p in cs.pods.values()},
        "nominations": nominated,
        "survivors": sorted(p.name for p in cs.pods.values()
                            if p.name.startswith("init-")),
    }


@pytest.mark.parametrize("templates", (
    (("hi", 3000),), (("hi", 3000), ("wide", 3500))),
    ids=("one_template", "two_templates_taking_turns"))
def test_a_batch_of_preemptors_ends_as_the_host_scheduler_ends_it(templates):
    sched, rec, got = _preempt_run(True, templates)
    _host, _rec, want = _preempt_run(False, templates)
    assert got == want
    assert len(got["nominations"]) == 6
    assert all(node for name, node in got["bindings"].items()
               if not name.startswith("init-"))
    assert len(got["survivors"]) < 32
    assert sched.host_path_pods == 0
    # every retry bound on its nominated node, first and alone
    evals = sched.metrics.nominated_evaluations
    assert (evals.value("bound"), evals.value("fell_through")) == (6, 0)
    # the counter: every acquisition of a template kept but, at most, the
    # template's first dry run; every nominated evaluation kept
    plans = _plans(sched)
    assert plans.get(("nominated", "built"), 0) == 0
    assert plans[("nominated", "kept")] == 6
    assert plans.get(("dry_run", "built"), 0) <= len(templates)
    assert plans[("dry_run", "kept")] + plans.get(("dry_run", "built"), 0) == 6
    # the stages say it too
    said = [s for name, s in rec.opened if name == "sched.postfilter.preempt"]
    assert [s["plan"] for s in said].count("kept") == plans[("dry_run", "kept")]
    assert all(s["plan_ms"] >= 0 for s in said)
    said = [s for name, s in rec.opened if name == "sched.nominated.eval"]
    assert [s["plan"] for s in said] == ["kept"] * 6
    assert [s["outcome"] for s in said] == ["bound"] * 6
    # session starts are counted as before: none of the 12 acquisitions is one
    assert sched.plan_rebuilds_full + sched.plan_rebuilds_delta \
        + sched.plan_rebuilds_resume == sum(
            1 for name, _ in rec.opened if name == "sched.plan.build")
    # each template has its own entry, holding its own request
    kept = {int(np.asarray(e.plan.features.request)[0])
            for e in sched._plans.values()}
    assert {milli for _stem, milli in templates} <= kept
    assert sched.device_breaker.consecutive_failures == 0


def test_the_keeper_holds_a_bounded_number_of_templates():
    from kubernetes_tpu.models import tpu_scheduler
    sched, cs = _device()
    for i in range(tpu_scheduler._KEPT_PLANS + 3):
        pre = _pod(f"pre-{i}", cpu=f"{3000 + i}m", priority=10)
        assert _acquire(sched, pre) == "built"
    assert len(sched._plans) == tpu_scheduler._KEPT_PLANS
    # the newest are the ones kept
    assert _acquire(sched, pre) == "kept"
    assert _acquire(sched, _pod("pre-0", cpu="3000m", priority=10)) == "built"
