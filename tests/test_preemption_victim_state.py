"""The victim tensors of the preemption what-if as state that follows the
snapshot by its generations (`ops/features.py` `PreemptionVictims`, PR 44):
whatever happened to the cluster since the last preemptor, the holder gives
what a build from every pod gives (arrays alike in shape, dtype and value, the
PodInfos the same objects in the same order); it derives again only the rows
whose node changed, and says how many; a change of anything its rows do not
cover (the preemptor's priority, the mirror's widths, the victim tier) gives
the fresh build's shapes; and a batch of preemptors ends as it ends with the
plain function in the holder's place. No timing is asserted."""

import random

import numpy as np
import pytest

from kubernetes_tpu.core import Scheduler
from kubernetes_tpu.ops import features
from kubernetes_tpu.ops.device_state import NodeStateMirror
from kubernetes_tpu.ops.features import (PreemptionVictims, _pow2,
                                         _resource_vec,
                                         build_preemption_victims)
from kubernetes_tpu.testing.annotations import StageAnnotations
from kubernetes_tpu.testing.wrappers import make_node, make_pod


def _node(name, cpu="8", **more):
    return make_node().name(name).capacity(
        {"cpu": cpu, "memory": "32Gi", "pods": 110, **more}
    ).zone("zone-0").obj()


_ORDINAL = [0]


def _pod(name, cpu="100m", priority=0, on=None, **more):
    pod = (make_pod().name(name).uid(name)
           .req({"cpu": cpu, "memory": "100Mi", **more})
           .priority(priority).obj())
    _ORDINAL[0] += 1
    pod.creation_ts = float(_ORDINAL[0])
    if on is not None:
        pod.node_name = on      # created bound: no fit is asked
    return pod


def _plain(pod, snapshot, mirror):
    """`build_preemption_victims` as it was before PR 44, restated: every
    node's pods filtered, sorted and encoded anew."""
    prio = pod.priority
    potential = []
    for ni in snapshot.node_info_list:
        pis = [pi for pi in ni.pods if pi.pod.priority < prio]
        pis.sort(key=lambda pi: (-pi.pod.priority, pi.pod.creation_ts))
        potential.append(pis)
    kmax = max((len(pis) for pis in potential), default=0)
    if kmax == 0 or kmax > features.PREEMPT_K_CAP:
        return None
    k = _pow2(kmax, 8)
    reqs = [[pi.pod.resource_request() for pi in pis] for pis in potential]
    for rs in reqs:
        for r in rs:
            for name in r.scalar_resources:
                mirror.scalar_slot(name)
    vic_req = np.zeros((mirror.np_cap, k, mirror.r_slots), np.int64)
    vic_valid = np.zeros((mirror.np_cap, k), bool)
    for i, rs in enumerate(reqs):
        for j, r in enumerate(rs):
            vic_req[i, j] = _resource_vec(mirror, r)
            vic_valid[i, j] = True
    return vic_req, vic_valid, potential


def _same(got, want):
    if want is None:
        assert got is None
        return
    assert got is not None
    for a, b in zip(got[:2], want[:2]):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.array_equal(a, b)
    assert len(got[2]) == len(want[2])
    for row, (mine, theirs) in enumerate(zip(got[2], want[2])):
        assert len(mine) == len(theirs), row
        assert all(a is b for a, b in zip(mine, theirs)), row


def _synced(sched, mirror):
    sched.cache.update_snapshot(sched.snapshot)
    mirror.sync(sched.snapshot.node_info_list)
    return sched.snapshot


def _check(holder, pre, sched, mirror):
    snapshot = _synced(sched, mirror)
    want = _plain(pre, snapshot, mirror)
    _same(holder.build(pre, snapshot), want)
    _same(build_preemption_victims(pre, snapshot, mirror), want)
    return want


# -- (1) the property the change stands on -------------------------------------

@pytest.mark.parametrize("priority", (-7, 0, 4))
@pytest.mark.parametrize("seed", (1, 2, 3, 4))
def test_after_any_churn_the_holder_gives_what_a_build_from_every_pod_gives(
        seed, priority):
    """Pod binds, pod deletes, a pod's priority update, node adds and node
    removes in a seeded random order, each followed by `update_snapshot`: the
    kept state, patched, against the plain build and the build from nothing.
    Node removes shift a tail of rows; the binds push one node past a
    victim tier and the deletes bring it back."""
    rng = random.Random(seed)
    sched = Scheduler(deterministic_ties=True)
    cs = sched.clientset
    mirror = NodeStateMirror()
    holder = PreemptionVictims(mirror)
    pre = _pod("pre", cpu="6", priority=priority)
    serial = [0]

    def bind(node):
        serial[0] += 1
        pod = _pod(f"p{serial[0]}", cpu=f"{rng.choice((100, 300))}m",
                   priority=rng.choice((-10, -5, 0, 3)))
        pod.node_selector = {"kubernetes.io/hostname": node}
        cs.create_pod(pod)
        sched.run_until_idle()

    for i in range(10):
        cs.create_node(_node(f"n{i}"))
    for i in range(10):
        for _ in range(rng.randint(0, 5)):
            bind(f"n{i}")
    _check(holder, pre, sched, mirror)
    partial = tiers = 0
    for step in range(60):
        op = rng.choice(("bind", "bind", "bind", "delete", "delete",
                         "priority", "node_add", "node_remove", "burst"))
        bound = [p for p in cs.pods.values() if p.node_name in cs.nodes]
        # one node takes the bursts, and the deletes once it holds ten: it
        # crosses a victim tier, both ways
        hot = "n0"
        on_hot = [p for p in bound if p.node_name == hot]
        if op == "bind":
            bind(rng.choice(sorted(cs.nodes)))
        elif op == "burst":
            for _ in range(5):
                bind(hot)
        elif op == "delete" and bound:
            for pod in (rng.sample(on_hot, len(on_hot) - 6)
                        if len(on_hot) > 9 else [rng.choice(bound)]):
                cs.delete_pod(pod)
        elif op == "priority" and bound:
            pod = rng.choice(bound)
            pod.priority = rng.choice((-10, -5, 0, 3))
            cs.update_pod(pod)
        elif op == "node_add":
            cs.create_node(_node(f"added-{step}"))
        elif op == "node_remove" and len(cs.nodes) > 4:
            cs.delete_node(rng.choice(sorted(set(cs.nodes) - {hot})))
        before = (None if holder._vic_valid is None
                  else holder._vic_valid.shape[1])
        want = _check(holder, pre, sched, mirror)
        partial += 0 < holder.rebuilt < len(sched.snapshot.node_info_list)
        tiers += (want is not None and before is not None
                  and want[1].shape[1] != before)
        # nothing happened: nothing is derived again
        _check(holder, pre, sched, mirror)
        assert holder.rebuilt == 0 or want is None
    assert partial > 10, "the walk never patched: every call was a full build"
    if priority == 4:
        assert tiers > 1, "no step crossed a victim tier and came back"


# -- (2) one node changed: one row ---------------------------------------------

def _device(nodes, low=-10, per_node=4, cpu="4"):
    """A `TPUScheduler` over `nodes` nodes, each kept full by `per_node` pods
    of 900m at a low priority (the shape of `preempt-5k`)."""
    from kubernetes_tpu.models import TPUScheduler
    sched = TPUScheduler()
    cs = sched.clientset
    for i in range(nodes):
        cs.create_node(_node(f"n{i}", cpu=cpu))
    for i in range(nodes):
        for j in range(per_node):
            cs.create_pod(_pod(f"init-{i}-{j}", cpu="900m", priority=low,
                               on=f"n{i}"))
    sched.run_until_idle()
    return sched, cs


def _what_if(sched, pre):
    """The device's dry run inside a `postfilter.preempt` stage that is
    heard; returns (candidates, what the stage said)."""
    rec = sched.stages._annotation = StageAnnotations()
    fw = sched.framework_for_pod(pre)
    n = len(sched.clientset.nodes)
    with sched.stages.stage("postfilter.preempt"):
        found = sched.device_dry_run_preemption(fw, None, pre, {}, n, 0)
    said = [s for name, s in rec.opened if name == "sched.postfilter.preempt"]
    return found, said[-1]


def _untroubled(sched):
    assert sched.device_breaker.consecutive_failures == 0
    assert sched.device_breaker.allows()
    assert not sched.metrics.device_path_fallback._values


def test_evicting_three_pods_of_one_node_rebuilds_exactly_one_row():
    sched, cs = _device(6)
    pre = _pod("pre", cpu="3", priority=10)
    rows = sched.metrics.preemption_victim_rows
    found, said = _what_if(sched, pre)
    assert len(found) == 6 and said["victim_rows_rebuilt"] == 6
    assert (rows.value("rebuilt"), rows.value("kept")) == (6, 0)
    for j in range(3):
        cs.delete_pod(cs.pods[f"init-2-{j}"])
    found, said = _what_if(sched, pre)
    assert said["victim_rows_rebuilt"] == 1 and said["victims_ms"] >= 0
    assert (rows.value("rebuilt"), rows.value("kept")) == (7, 5)
    # the emptied node holds the preemptor without a victim: no candidate
    assert sorted(c.node_name for c in found) == ["n0", "n1", "n3", "n4", "n5"]
    assert all(len(c.victims) == 3 for c in found)
    # and nothing at all between two calls: every row kept
    found, said = _what_if(sched, pre)
    assert said["victim_rows_rebuilt"] == 0
    assert (rows.value("rebuilt"), rows.value("kept")) == (7, 11)
    _untroubled(sched)


# -- (3) what the rows do not cover --------------------------------------------

def _another_priority(sched, cs, pre):
    # init pods of -10 are victims of both; the -5s only of the second
    for i in range(4):
        cs.create_pod(_pod(f"mid-{i}", cpu="100m", priority=-5, on=f"n{i}"))
    return _pod("pre-low", cpu="3", priority=-5)


def _grown_r_slots(sched, cs, pre):
    # five never-seen scalar resources on a victim: past the mirror's four
    cs.create_pod(_pod("odd", priority=-10, on="n1", **{
        f"example.com/thing-{i}": 1 for i in range(5)}))
    return pre


def _grown_np_cap(sched, cs, pre):
    for i in range(sched.mirror.np_cap):
        cs.create_node(_node(f"extra-{i}", cpu="4"))
    cs.create_pod(_pod("far", cpu="900m", priority=-10, on="extra-3"))
    return pre


def _crossed_k_tier(sched, cs, pre):
    for i in range(6):          # 4 + 6 victims on n2: the tier of 16
        cs.create_pod(_pod(f"more-{i}", cpu="10m", priority=-10, on="n2"))
    return pre


@pytest.mark.parametrize("change", (_another_priority, _grown_r_slots,
                                    _grown_np_cap, _crossed_k_tier),
                         ids=lambda f: f.__name__.lstrip("_"))
def test_a_change_the_rows_do_not_cover_gives_the_fresh_builds_shapes(change):
    sched, cs = _device(6)
    pre = _pod("pre", cpu="3", priority=10)
    found, said = _what_if(sched, pre)
    was = (said["rows"], said["k"], said["r"])
    assert was == (64, 8, 7) and len(found) == 6
    pre = change(sched, cs, pre)
    found, said = _what_if(sched, pre)
    assert found, "the dry run gave up: a shape error lands there"
    _untroubled(sched)
    holder = sched._victims
    want = _plain(pre, sched.snapshot, sched.mirror)
    _same((holder._vic_req, holder._vic_valid, holder._potential), want)
    now = (said["rows"], said["k"], said["r"])
    assert now == want[1].shape + want[0].shape[2:]
    grew = {"another_priority": (64, 8, 7), "grown_r_slots": (64, 8, 11),
            "grown_np_cap": (128, 8, 7), "crossed_k_tier": (64, 16, 7)}
    assert now == grew[change.__name__.lstrip("_")]
    # back under the tier: the shapes a fresh build gives, again
    if change is _crossed_k_tier:
        for i in range(6):
            cs.delete_pod(cs.pods[f"more-{i}"])
        found, said = _what_if(sched, pre)
        assert (said["rows"], said["k"], said["r"]) == was
        assert said["victim_rows_rebuilt"] == 1
        _same((holder._vic_req, holder._vic_valid, holder._potential),
              _plain(pre, sched.snapshot, sched.mirror))
        _untroubled(sched)


def test_a_scalar_resource_first_met_inside_the_walk_starts_again_wider():
    """A victim's never-seen scalar resource that the mirror has not met
    (its node was not synced since): interning it inside the walk grows
    `r_slots`, and the walk starts again at the new width."""
    sched = Scheduler(deterministic_ties=True)
    cs = sched.clientset
    mirror = NodeStateMirror()
    holder = PreemptionVictims(mirror)
    for i in range(3):
        cs.create_node(_node(f"n{i}"))
        cs.create_pod(_pod(f"low-{i}", priority=-1, on=f"n{i}"))
    pre = _pod("pre", cpu="6", priority=5)
    _check(holder, pre, sched, mirror)
    assert holder._vic_req.shape == (64, 8, 7)
    cs.create_pod(_pod("odd", priority=-1, on="n1", **{
        f"example.com/thing-{i}": 1 for i in range(5)}))
    sched.cache.update_snapshot(sched.snapshot)     # and no mirror.sync
    got = holder.build(pre, sched.snapshot)
    assert mirror.r_slots == 11 and got[0].shape == (64, 8, 11)
    assert holder.rebuilt == 3          # the walk that counts is the second
    _same(got, _plain(pre, sched.snapshot, mirror))


# -- (4) nothing to give -------------------------------------------------------

@pytest.mark.parametrize("why", ("nobody_is_lower", "past_the_k_cap"))
def test_none_is_still_none_and_leaves_no_state_behind(why, monkeypatch):
    sched, cs = _device(4)
    pre = _pod("pre", cpu="3", priority=10)
    found, _ = _what_if(sched, pre)
    assert len(found) == 4 and sched._victims._key is not None
    fw = sched.framework_for_pod(pre)
    if why == "nobody_is_lower":
        nobody = _pod("meek", cpu="3", priority=-10)
        assert sched.device_dry_run_preemption(fw, None, nobody, {}, 4, 0) \
            is None
    else:
        monkeypatch.setattr(features, "PREEMPT_K_CAP", 3)
        assert sched.device_dry_run_preemption(fw, None, pre, {}, 4, 0) is None
        monkeypatch.undo()
    holder = sched._victims
    assert holder._key is None and holder._vic_req is None
    assert not holder._names and not holder._potential
    cs.delete_pod(cs.pods["init-1-0"])
    found, said = _what_if(sched, pre)
    assert len(found) == 4 and said["victim_rows_rebuilt"] == 4
    _same((holder._vic_req, holder._vic_valid, holder._potential),
          _plain(pre, sched.snapshot, sched.mirror))
    _untroubled(sched)


# -- (5) a batch of preemptors in one turn -------------------------------------

def _a_batch_of_preemptors(preemptors=4):
    sched, cs = _device(8)
    rec = sched.stages._annotation = StageAnnotations()
    for i in range(preemptors):
        cs.create_pod(_pod(f"hi-{i}", cpu="3", priority=10))
    nominated = {}
    for _ in range(400):
        if not sched.schedule_one():
            break
        for p in cs.pods.values():
            if p.nominated_node_name:
                nominated.setdefault(p.name, p.nominated_node_name)
    sched.run_until_idle()
    said = [s for name, s in rec.opened if name == "sched.postfilter.preempt"]
    return sched, {
        "bindings": {p.name: p.node_name for p in cs.pods.values()},
        "nominations": nominated,
        "victims": [s["victims"] for s in said],
        "survivors": sorted(p.name for p in cs.pods.values()
                            if p.name.startswith("init-")),
    }, said


def test_a_batch_of_preemptors_ends_as_with_the_plain_function(monkeypatch):
    sched, kept, said = _a_batch_of_preemptors()
    assert kept["victims"] == [3, 3, 3, 3] and len(kept["nominations"]) == 4
    assert all(kept["bindings"][f"hi-{i}"] for i in range(4))
    assert len(set(kept["nominations"].values())) == 4
    assert sched.host_path_pods == 0
    runs = sched.metrics.preemption_dry_runs
    assert (runs.value("device"), runs.value("host")) == (4, 0)
    # the first finds every row new, each later one the node just emptied
    assert [s["victim_rows_rebuilt"] for s in said] == [8, 1, 1, 1]
    rows = sched.metrics.preemption_victim_rows
    assert (rows.value("rebuilt"), rows.value("kept")) == (11, 21)
    _untroubled(sched)
    monkeypatch.setattr(
        PreemptionVictims, "build",
        lambda self, pod, snapshot: _plain(pod, snapshot, self.mirror))
    plain_sched, plain, _ = _a_batch_of_preemptors()
    assert plain_sched.metrics.preemption_victim_rows.value("rebuilt") == 0
    assert plain == kept
