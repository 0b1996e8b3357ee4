"""A plan over a narrowed row set (PR 47): what `KeptPlan.derive(rows=...)`
gathers and what it leaves whole, the state that goes with it, what the
session says of it (`narrowed` on `queue.pop`, `narrowed_rows` / `plan_rows`
on `plan.build` and `device.dispatch`, the counter by path), that `warm_for`
leaves nothing to compile, and what a narrowed session does not do: hand back
a tail, install a hint, patch rows."""

import numpy as np
import pytest

from kubernetes_tpu.compile_cache import COMPILE_EVENTS
from kubernetes_tpu.core import FakeClientset
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.ops.features import (ROW_FIELDS, narrow_width,
                                         narrowed_rows, padded_rows)
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.testing.annotations import StageAnnotations


def _scheduler(monkeypatch=None, nodes=20, **kw):
    cs = FakeClientset()
    sched = TPUScheduler(clientset=cs, **kw)
    opened = None
    if monkeypatch is not None:
        rec = StageAnnotations()
        monkeypatch.setattr(sched.stages, "_annotation", rec)
        opened = rec.opened
    for i in range(nodes):
        cs.create_node(make_node().name(f"n{i}").capacity(
            {"cpu": 8, "memory": "16Gi", "pods": 110}).zone(f"z{i % 4}")
            .label("disk", "ssd" if i % 2 else "hdd").obj())
    return sched, cs, opened


def _pinned(name, *nodes, cpu="100m"):
    b = make_pod().name(name).req({"cpu": cpu})
    for n in nodes:
        b = b.node_affinity_name(n)      # one term a node: the terms are ORed
    return b.obj()


def test_the_row_fields_are_the_fields_the_mesh_shards_over_the_nodes():
    from jax.sharding import PartitionSpec as P
    from kubernetes_tpu.parallel.mesh import _feature_specs
    specs = _feature_specs("nodes")
    per_node = {name for name in specs._fields
                if getattr(specs, name) != P()}
    # the nominated lane is derived at every use, on the host: not gathered
    assert per_node - {"nom_req", "nom_pods"} == set(ROW_FIELDS)


def test_the_rows_are_the_named_nodes_in_snapshot_order():
    row_of = {f"n{i}": i for i in range(10)}
    assert narrowed_rows(_pinned("p", "n7", "n2", "n5"), row_of) == [2, 5, 7]
    assert narrowed_rows(_pinned("p", "gone"), row_of) == []
    assert narrowed_rows(_pinned("p", "gone", "n3"), row_of) == [3]
    assert narrowed_rows(make_pod().name("plain").obj(), row_of) is None
    assert [narrow_width(m) for m in (0, 1, 64, 65, 120)] \
        == [64, 64, 64, 128, 128]
    assert narrow_width(1, shards=8) == 64 and narrow_width(1, shards=6) == 66
    assert padded_rows((4, 9), 8).tolist() == [4, 9, 0, 0, 0, 0, 0, 0]


def test_a_narrowed_plan_is_the_full_plans_rows_and_the_clusters_tables():
    sched, cs, _ = _scheduler()
    for i in range(6):                      # some load, unevenly
        cs.create_pod(make_pod().name(f"w{i}").req({"cpu": "1"}).obj())
    sched.run_until_idle()
    pod = _pinned("ds", "n11", "n3", "n16")
    fw = sched.framework_for_pod(pod)
    sched.next_start_node_index = 7
    state, plan = sched.build_plan(fw, pod, sched.max_batch)
    full_state, full = sched._build_full_plan(fw, pod, sched.max_batch)
    assert plan.rows == (3, 11, 16) and full.rows is None
    assert plan.narrowed_attrs() == {"narrowed_rows": 3, "plan_rows": 64}
    assert full.narrowed_attrs() == {}
    f, g = plan.features, full.features
    assert (int(f.num_nodes), int(f.to_find), int(f.start_index)) == (3, 3, 1)
    assert int(g.num_nodes) == 20 and int(g.start_index) == 7
    for name in ROW_FIELDS:
        got, want = np.asarray(getattr(f, name)), np.asarray(getattr(g, name))
        assert got.shape == (64,) + want.shape[1:], name
        if name != "extra_ok":
            assert np.array_equal(got[:3], want[[3, 11, 16]]), name
    # the named rows keep their verdict, the padding is refused
    assert np.asarray(f.extra_ok).tolist() == [True] * 3 + [False] * 61
    assert np.asarray(f.sel_match)[:3].all()
    assert not np.asarray(g.sel_match)[[0, 5, 19]].any()
    # everything that is not a row stays the cluster's
    for name in f._fields:
        if name in ROW_FIELDS + ("num_nodes", "to_find", "start_index",
                                 "nom_req", "nom_pods"):
            continue
        assert np.array_equal(np.asarray(getattr(f, name)),
                              np.asarray(getattr(g, name))), name
    for name in ("batch_pad", "fit_strategy", "vmax", "has_pns", "pod_local",
                 "has_nom", "engine", "rides_lap"):
        assert getattr(plan, name) == getattr(full, name), name
    # the state is the same three rows, valid, and nothing else
    for name in state._fields:
        got, want = np.asarray(getattr(state, name)), np.asarray(
            getattr(full_state, name))
        if name == "topo":
            assert np.array_equal(got[:, :3], want[:, [3, 11, 16]])
        elif name == "valid":
            assert got.tolist() == [True] * 3 + [False] * 61
        else:
            assert np.array_equal(got[:3], want[[3, 11, 16]]), name


def test_the_sample_is_cut_at_120_named_nodes():
    sched, cs, _ = _scheduler(nodes=130)
    pod = _pinned("ds", *[f"n{i}" for i in range(5, 125)])
    state, plan = sched.build_plan(sched.framework_for_pod(pod), pod, 8)
    f = plan.features
    assert (int(f.num_nodes), int(f.to_find)) == (120, 100)
    assert state.valid.shape == (128,) and len(plan.rows) == 120


def test_a_narrowed_session_says_what_it_is_and_counts_its_pods(monkeypatch):
    sched, cs, opened = _scheduler(monkeypatch, max_batch=16)
    proto = _pinned("proto", "n4")
    for i in range(40):
        cs.create_pod(proto.clone_from_template(f"ds-{i}"))
    for i in range(10):
        cs.create_pod(make_pod().name(f"plain-{i}").req({"cpu": "100m"}).obj())
    sched.run_until_idle()
    assert sched.host_path_pods == 0 and sched.scheduled == 50
    assert {p.node_name for p in cs.pods.values()
            if p.name.startswith("ds-")} == {"n4"}
    pops = [s for name, s in opened if name == "sched.queue.pop"]
    assert sum(p["narrowed"] for p in pops) == 40
    assert sum(p["pods"] for p in pops) == 50
    assert all(p["narrowed"] in (0, p["pods"]) for p in pops)
    builds = [s for name, s in opened if name == "sched.plan.build"]
    assert [(b.get("narrowed_rows"), b.get("plan_rows")) for b in builds] \
        == [(1, 64), (None, None)]
    dispatches = [s for name, s in opened if name == "sched.device.dispatch"]
    narrowed = [d for d in dispatches if "narrowed_rows" in d]
    assert [(d["batch"], d["narrowed_rows"], d["plan_rows"])
            for d in narrowed] == [(16, 1, 64), (16, 1, 64), (8, 1, 64)]
    assert len(dispatches) == 4 and "plan_rows" not in dispatches[-1]
    count = sched.metrics.prefilter_narrowed_pods
    assert (count.value("device"), count.value("host")) == (40, 0)


def test_a_narrowed_session_hands_back_no_tail_and_installs_no_hint():
    sched, cs, _ = _scheduler()
    proto = _pinned("proto", "n4")
    for wave in range(2):
        for i in range(12):
            cs.create_pod(proto.clone_from_template(f"ds-{wave}-{i}"))
        sched.run_until_idle()
    assert sched.host_path_pods == 0 and sched.scheduled == 24
    # every session of the template is a full build: nothing to resume from
    assert (sched.plan_rebuilds_full, sched.plan_rebuilds_resume,
            sched.plan_rebuilds_delta) == (2, 0, 0)
    assert not [e for e in sched._plans.values() if e.tail_seq is not None]
    assert sched._hints.entry is None and sched.hint_hits == 0
    # the kept plan is the one over every row: a preemptor derives from it
    (entry,) = sched._plans.values()
    assert entry.plan.rows is None and entry.guard is not None
    # the mirror heard of the 24 pods the ordinary way
    sched._sync_mirror()
    assert sched.mirror.h_pod_count[4] == 24
    assert int(np.asarray(sched.mirror.flush().pod_count)[4]) == 24


def test_a_narrowed_session_ends_at_an_event_that_would_patch_rows():
    """A bound pod deleted under a live narrowed session: its rows are not
    the mirror's, so the session ends there and the next one builds anew
    from the patched truth, with the same placements as the host's."""
    sched, cs, _ = _scheduler(max_batch=4)
    cs.create_pod(make_pod().name("old").req({"cpu": "1"}).obj())
    sched.run_until_idle()
    proto = _pinned("proto", "n4", "n9")
    for i in range(12):
        cs.create_pod(proto.clone_from_template(f"ds-{i}"))
    fired = []

    def hook(where):
        if where == "dispatch" and len(fired) == 1:
            cs.delete_pod(next(p for p in cs.pods.values()
                               if p.name == "old"))
        fired.append(where)
    sched._fault_hook = hook
    sched.run_until_idle()
    sched._fault_hook = None
    assert sched.scheduled == 13 and sched.host_path_pods == 0
    assert sched.plan_rebuilds_full >= 3 and sched.plan_rebuilds_delta == 0
    assert sched.device_breaker.consecutive_failures == 0


def test_warm_for_leaves_a_narrowed_session_nothing_to_compile():
    sched, cs, _ = _scheduler(max_batch=16)
    proto = _pinned("proto", "n4")
    sched.warm_for(proto)
    before = COMPILE_EVENTS[0]
    for i in range(40):
        cs.create_pod(proto.clone_from_template(f"ds-{i}"))
    sched.run_until_idle()
    assert sched.scheduled == 40 and sched.host_path_pods == 0
    assert COMPILE_EVENTS[0] == before


@pytest.mark.parametrize("missing", ["gone"])
def test_a_pin_to_nobody_is_diagnosed_from_the_narrowed_rows(missing):
    sched, cs, _ = _scheduler()
    for i in range(3):
        cs.create_pod(_pinned(f"lost-{i}", missing))
    sched.run_until_idle()
    assert sched.scheduled == 0 and sched.failures == 3
    assert sched.host_path_pods == 0
    assert sched.next_start_node_index == 0


def test_a_pin_that_names_nobody_stays_on_the_host_path():
    from kubernetes_tpu.api.labels import IN, Requirement
    from kubernetes_tpu.api.types import (Affinity, NodeAffinity,
                                          NodeSelector, NodeSelectorTerm)
    from kubernetes_tpu.ops.features import batch_supported
    pod = make_pod().name("p").obj()
    pod.affinity = Affinity(node_affinity=NodeAffinity(required=NodeSelector((
        NodeSelectorTerm(match_fields=(
            Requirement("metadata.name", IN, ()),)),))))
    assert batch_supported(pod, None) == "node-affinity names no node"
    assert batch_supported(_pinned("q", "n1"), None) is None
