"""Cluster events, pending pods and node groups in the benchmark's reference
(benchmark/reference.py, run.py's replay, objects.py, control.py): the
source's semantics on a case worked out by hand, each refusal, the three event
controls shown to fail, the five configurations' clusters held to what they
were, and the toy of tests/benchmark/toy_bench (two node groups in three
zones, nodes coming and going, a pod that can never fit) through `run.py
--rehearse` against both of the program's schedulers. No timing is asserted."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import control  # noqa: E402
import objects  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

TOY_BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "toy_bench")
TOY_MANIFEST = os.path.join(TOY_BENCH, "manifest.json")
SEEDS = (7, 11, 3000000019)      # the driver's seeds exceed 32 signed bits


def _node(name, zone, cpu=4000, memory=8 << 30, pods=110):
    return {"name": name, "zone": zone, "cpu": cpu, "memory": memory,
            "pods": pods}


def _toy(name, rehearse=True):
    return objects.load_config(
        os.path.join(TOY_BENCH, "configs", name + ".json"), rehearse)


# -- the source's semantics, by hand -----------------------------------------

# Equal nodes of 4 cpu / 8Gi, pods of 1 cpu / 2Gi: both fractions are equal
# on every node, so BalancedAllocation is 100 everywhere and LeastAllocated
# falls with every pod: a pod goes to the node with the fewest pods, the
# first of them in walk order. Under 100 nodes every node is walked, so the
# start index stays 0 and the walk is the node tree's list.
POD = {"cpu": 1, "memory": "2Gi"}
LARGE = {"cpu": 5, "memory": "1Gi"}
BY_HAND = [
    # tree z0: [a, c], z1: [b]; list a, b, c
    ("create", "p1", "pod", "a"), ("create", "p2", "pod", "b"),
    ("create", "p3", "pod", "c"),
    # d joins the end of z1's list: a, b, c, d
    ("node_add", "d", _node("d", "z1"), None), ("create", "p4", "pod", "d"),
    # a zone first met joins the end of the zones: a, b, e, c, d
    ("node_add", "e", _node("e", "z2"), None), ("create", "p5", "pod", "e"),
    ("create", "p6", "pod", "a"),
    # b leaves z1's list: a, d, e, c; p2 stays bound to b and counts nowhere
    ("node_delete", "b", None, None),
    ("create", "p7", "pod", "d"), ("create", "p8", "pod", "e"),
    ("create", "p9", "pod", "c"),
    # z2 is left empty and leaves the zones: a, d, c, two pods each
    ("node_delete", "e", None, None), ("create", "p10", "pod", "a"),
    # a frees a place; p2's delete accounts nothing (its node is gone)
    ("delete", "p1", None, None), ("delete", "p2", None, None),
    ("create", "p11", "pod", "a"),
    # 5 cpu fit no node of 4: pending, and the walk's start stays
    ("create", "big", "large", None), ("create", "p12", "pod", "d"),
    # z2 again, now the LAST zone: a, d, f, c; the empty node draws the pod
    ("node_add", "f", _node("f", "z2"), None), ("create", "p13", "pod", "f"),
    # a: 3, d: 3, f: 1, c: 2
    ("delete", "big", None, None), ("create", "p14", "pod", "f"),
]


def test_a_dozen_pods_by_hand():
    ref = reference.Reference(
        [_node("a", "z0"), _node("b", "z1"), _node("c", "z0")])
    assert ref.names == ["a", "b", "c"]
    log = [(op, name, arg) for op, name, arg, _ in BY_HAND]
    want = {name: node for op, name, _, node in BY_HAND if op == "create"}
    got = reference.replay(ref, {"pod": POD, "large": LARGE}, log, ["large"])
    assert got == want
    assert ref.names == ["a", "d", "f", "c"] and ref.zones == ["z0", "z1", "z2"]
    assert ref.start == 0 and ref.to_find == 4
    assert ref.pending == {} and ref.over_allocatable() == []
    # a: p6 p10 p11, d: p4 p7 p12, f: p13 p14, c: p3 p9; the pods of b and
    # e (p2, p5, p8) count nowhere
    assert ref.n_pods.tolist() == [3, 3, 2, 2]
    assert ref.req_cpu.tolist() == [3000, 3000, 2000, 2000]
    # the run's side of it: bound where the reference says, `big` unbound
    cmp_ = reference.compare(want, dict(want, big=""))
    assert (cmp_["differing"], cmp_["unbound"], cmp_["pending"]) == (0, 0, 1)
    cmp_ = reference.compare(want, dict(want, big="a", p14=None))
    assert (cmp_["differing"], cmp_["unbound"]) == (2, 1)
    assert cmp_["examples"][0] == ("big", None, "a")


def test_the_start_index_is_kept_and_taken_modulo_the_new_count():
    """130 equal nodes in one zone: the sample stops at 100 feasible nodes,
    so the first pod leaves the start at 100. With 40 nodes gone the next
    walk starts at 100 % 90, finds 90 (under 100 nodes all are wanted) and
    comes back to where it started."""
    nodes = reference.node_descriptions(
        {"cpu": 4, "memory": "8Gi", "pods": 10, "zones": 1}, 130, range(130))
    ref = reference.Reference(nodes)
    assert ref.to_find == 100
    assert ref.schedule("p0", POD) == "node-0" and ref.start == 100
    for i in range(90, 130):
        ref.remove_node(f"node-{i}")
    assert (ref.n, ref.to_find, ref.start) == (90, 90, 100)
    assert ref.schedule("p1", POD) == "node-10" and ref.start == 10
    ref.add_node(_node("late", "zone-0"))
    assert ref.names[-1] == "late" and ref.start == 10
    # 91 nodes: node-10 holds a pod, so the first maximum is the next node
    assert ref.schedule("p2", POD) == "node-11"


def test_a_removed_nodes_pods_leave_a_features_counts():
    """Hard zone spread, maxSkew 1, two zones: the pod on the removed node
    leaves its zone's count, and a zone first met counts from 0."""
    spread = dict(POD, labels={"app": "s"}, topologySpreadConstraints=[
        {"maxSkew": 1, "labelSelector": {"app": "s"}}])
    ref = reference.Reference([_node("a", "z0"), _node("b", "z1"),
                               _node("c", "z0"), _node("d", "z1")])
    got = [ref.schedule(f"p{i}", spread) for i in range(4)]
    assert got == ["a", "b", "c", "d"]
    ref.remove_node("b")                 # z0: 2, z1: 1 (p3 on d)
    assert ref.schedule("p4", spread) == "d"
    ref.remove_node("d")                 # z1 is gone: one zone, no skew
    assert ref.zones == ["z0"] and ref.schedule("p5", spread) == "a"
    ref.add_node(_node("e", "z2"))       # z0: 3, z2: 0
    assert [ref.schedule(f"q{i}", spread) for i in range(3)] == ["e"] * 3
    # z0: 3, z2: 3: both zones may take one; c holds the fewest pods
    assert ref.schedule("q3", spread) == "c"
    assert ref.schedule("q4", spread) == "e"     # z0 is one ahead
    assert ref.schedule("q5", spread) == "a"     # e is full (4 cpu)
    assert ref.over_allocatable() == []
    with pytest.raises(reference.Unschedulable):
        ref.schedule("q6", spread)       # z0 is one ahead, e is full


# -- refusals ----------------------------------------------------------------

def _result(log, **more):
    return dict({"nodes": [_node("a", "z0")], "templates": {"pod": POD},
                 "log": log, "placements": {}}, **more)


def test_an_unknown_log_operation_fails_the_run():
    """It used to be replayed as a pod's delete."""
    with pytest.raises(ValueError, match="node_cordon"):
        run.replay(_result([("create", "p", "pod"),
                            ("node_cordon", "a", None)]), BENCH)
    with pytest.raises(ValueError, match="described as"):
        run.replay(_result([("node_add", "b", _node("c", "z0"))]), BENCH)
    with pytest.raises(ValueError, match="may_pend"):
        run.replay(_result([], may_pend=["nosuch"]), BENCH)
    cmp_, over = run.replay(_result(
        [("create", "p", "pod"), ("node_delete", "a", None),
         ("delete", "p", None)], placements={"p": "a"}), BENCH)
    assert (cmp_["compared"], cmp_["differing"], over) == (1, 0, [])


def test_only_a_group_the_run_names_may_pend():
    ref = reference.Reference([_node("a", "z0")])
    with pytest.raises(reference.Unschedulable):
        ref.schedule("big", LARGE)
    assert ref.schedule("big", LARGE, may_pend=True) is None
    assert list(ref.pending) == ["big"]
    with pytest.raises(ValueError, match="twice"):
        ref.schedule("big", LARGE, may_pend=True)
    ref.delete("big")
    assert ref.pending == {}


SPREAD = dict(POD, labels={"app": "s"}, topologySpreadConstraints=[
    {"maxSkew": 1, "labelSelector": {"app": "s"}}])
# for each event that can admit a pending pod: the nodes, the log up to and
# with the event, the pending pod, and where its retry must land
ADMITTED = {
    # 5 cpu fit no node of 4; b, as small, changes nothing; c holds 8
    "node_add": (
        [_node("a", "z0")],
        [("create", "big", "large"), ("node_add", "b", _node("b", "z0")),
         ("node_add", "c", _node("c", "z0", cpu=8000))], "big", "c"),
    # a holds four pods of 1 cpu; the fifth waits for the first to go
    "delete": (
        [_node("a", "z0")],
        [("create", f"p{i}", "pod") for i in range(4)]
        + [("create", "p4", "waits"), ("delete", "p0", None)], "p4", "a"),
    # z0 is full and two pods ahead of z1: the spread pod fits nowhere
    # until z1 leaves the zones with its node, and c (z0) has room
    "node_delete": (
        [_node("a", "z0", cpu=2000), _node("b", "z1", cpu=1000)],
        [("create", "p0", "spread"), ("create", "p1", "spread"),
         ("create", "p2", "spread"), ("delete", "p1", None),
         ("create", "plain", "one"), ("create", "p3", "spreadwaits"),
         ("node_add", "c", _node("c", "z0", cpu=1000)),
         ("node_delete", "b", None)], "p3", "c"),
}
TEMPLATES = {"pod": POD, "waits": dict(POD), "large": LARGE,
             "spread": SPREAD, "spreadwaits": dict(SPREAD),
             "one": {"cpu": 1}}
MAY_PEND = ["large", "waits", "spreadwaits"]


@pytest.mark.parametrize("event", sorted(ADMITTED))
def test_a_pending_pod_that_becomes_feasible_is_unmodelled(event):
    """No `retry` in the log: the refusal stands, and names what is
    missing."""
    nodes, log, pod, _ = ADMITTED[event]
    with pytest.raises(reference.Unmodelled,
                       match=f"pending pod {pod} .* no `retry`"):
        reference.replay(reference.Reference(nodes), TEMPLATES, log,
                         MAY_PEND)
    # up to the event the log is sound, the pod pending
    expected = reference.replay(reference.Reference(nodes), TEMPLATES,
                                log[:-1], MAY_PEND)
    assert expected[pod] is None


@pytest.mark.parametrize("event", sorted(ADMITTED))
def test_a_pending_pod_that_the_log_retries_lands_where_it_is_retried(event):
    """The same logs with the `retry` the scheduler made once the event had
    requeued the pod: it lands, and not before."""
    nodes, log, pod, node = ADMITTED[event]
    ref = reference.Reference(nodes)
    expected = reference.replay(ref, TEMPLATES,
                                log + [("retry", pod, None)], MAY_PEND)
    assert expected[pod] == node and ref.pending == {}
    assert ref.over_allocatable() == []
    # a retry ahead of the event finds no node and changes nothing; the one
    # behind it lands as before
    early = log[:-1] + [("retry", pod, None), log[-1], ("retry", pod, None)]
    assert reference.replay(reference.Reference(nodes), TEMPLATES, early,
                            MAY_PEND) == expected
    # and a retry of a pod that is not pending is an error
    with pytest.raises(ValueError, match="not pending"):
        reference.replay(reference.Reference(nodes), TEMPLATES,
                         log + [("retry", pod, None)] * 2, MAY_PEND)


def test_what_a_node_event_must_not_pass():
    ref = reference.Reference([_node("a", "z0"), _node("b", "z0")])
    ref.schedule("p", POD)
    with pytest.raises(ValueError, match="duplicate"):
        ref.add_node(_node("b", "z1"))
    with pytest.raises(KeyError):
        ref.remove_node("nosuch")
    with pytest.raises(reference.Unmodelled):
        ref.add_node(dict(_node("c", "z0"), taints=[{"key": "k"}]))
    ref.remove_node("a")
    # the source's cache would hand the node its old pods back
    with pytest.raises(reference.Unmodelled, match="created again"):
        ref.add_node(_node("a", "z0"))
    ref.delete("p")
    ref.add_node(_node("a", "z0"))
    assert ref.names == ["b", "a"]
    with pytest.raises(KeyError):
        ref.delete("p")


# -- node groups -------------------------------------------------------------

SMALL = {"cpu": 4, "memory": "32Gi", "pods": 110, "zones": 3}
LARGER = {"cpu": 8, "memory": "64Gi", "pods": 110, "zones": 3}


def test_node_groups_are_numbered_across_and_permuted_whole():
    groups = [{"count": 3, "template": SMALL},
              {"count": 1, "template": LARGER, "name": "the-big-one"},
              {"count": 2, "template": LARGER}]
    got = reference.group_descriptions(groups, range(6))
    assert [n["name"] for n in got] == [
        "node-0", "node-1", "node-2", "the-big-one", "node-3", "node-4"]
    assert [n["zone"] for n in got] == [f"zone-{k % 3}" for k in range(6)]
    assert [n["cpu"] for n in got] == [4000] * 3 + [8000] * 3
    order = objects.node_order(6, 3000000019)
    assert sorted(order) == list(range(6)) and order != list(range(6))
    cfg = {"nodes": groups}
    assert objects.cluster(cfg, 3000000019) == [got[i] for i in order]
    # one group reads as it always did
    assert reference.group_descriptions(groups[:1], [2, 0, 1]) == \
        reference.node_descriptions(SMALL, 3, [2, 0, 1])
    with pytest.raises(ValueError, match="group of one"):
        reference.group_descriptions(
            [{"count": 2, "template": SMALL, "name": "x"}], range(2))
    with pytest.raises(reference.Unmodelled):
        reference.group_descriptions(
            [{"count": 1, "template": SMALL, "labels": {}}], range(1))
    with pytest.raises(ValueError, match="permutation"):
        reference.group_descriptions(groups, range(5))


def test_rehearse_gives_a_count_for_each_group(tmp_path):
    cfg = {"nodes": [{"count": 5000, "template": SMALL},
                     {"count": 10000, "template": LARGER}],
           "initPods": {"count": 1000, "template": POD},
           "rehearse": {"nodes": [20, 10], "initPods": 5}}
    path = tmp_path / "two.json"
    path.write_text(json.dumps(cfg))
    toy = objects.load_config(str(path), rehearse=True)
    assert [g["count"] for g in toy["nodes"]] == [20, 10]
    assert toy["initPods"]["count"] == 5
    assert len(objects.cluster(toy, 7)) == 30
    full = objects.load_config(str(path), rehearse=False)
    assert [g["count"] for g in full["nodes"]] == [5000, 10000]
    cfg["rehearse"]["nodes"] = [20]
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="counts"):
        objects.load_config(str(path), rehearse=True)


# `objects.cluster` of the five configurations at their own size, as the
# parent commit (f6e5d08) gave it: sha256 of its JSON. Node groups change none.
CLUSTERS = {
    ("spread-5k", 7): "f59fc498006133413e8e51a739dced172490819b5fd06b3f50afd2c9e9066a7e",
    ("spread-5k", 11): "310fc2ac2cdaab49f91846262790e91589736f2542b55c1997115b3eaa058011",
    ("spread-5k", 3000000019): "d047c9fca21d260388225da21d1a8b1deeddf89dac476d962e3c80122e3cab94",
    ("basic-5k", 7): "f59fc498006133413e8e51a739dced172490819b5fd06b3f50afd2c9e9066a7e",
    ("basic-5k", 11): "310fc2ac2cdaab49f91846262790e91589736f2542b55c1997115b3eaa058011",
    ("basic-5k", 3000000019): "d047c9fca21d260388225da21d1a8b1deeddf89dac476d962e3c80122e3cab94",
    ("antiaffinity-5k", 7): "36dcd088a6b14f28e2a24037d84724a383a8e584c3f7ba10ad03a2035930cbf3",
    ("antiaffinity-5k", 11): "c212c9abd4e2244e249ed4af3c2c29b144f3cbc6bf969021470352e7a52b66d8",
    ("antiaffinity-5k", 3000000019): "0bc293ab7bc254ee42da4e20230f0d2375ba71e79b9c7eb8fb8cbb1ce8d55a68",
    ("prefaffinity-5k", 7): "36dcd088a6b14f28e2a24037d84724a383a8e584c3f7ba10ad03a2035930cbf3",
    ("prefaffinity-5k", 11): "c212c9abd4e2244e249ed4af3c2c29b144f3cbc6bf969021470352e7a52b66d8",
    ("prefaffinity-5k", 3000000019): "0bc293ab7bc254ee42da4e20230f0d2375ba71e79b9c7eb8fb8cbb1ce8d55a68",
    ("basic-5k-50k", 7): "36dcd088a6b14f28e2a24037d84724a383a8e584c3f7ba10ad03a2035930cbf3",
    ("basic-5k-50k", 11): "c212c9abd4e2244e249ed4af3c2c29b144f3cbc6bf969021470352e7a52b66d8",
    ("basic-5k-50k", 3000000019): "0bc293ab7bc254ee42da4e20230f0d2375ba71e79b9c7eb8fb8cbb1ce8d55a68",
}


@pytest.mark.parametrize("name, seed", sorted(CLUSTERS))
def test_the_five_clusters_are_byte_equal_to_the_parents(name, seed):
    cfg = objects.load_config(
        os.path.join(BENCH, "configs", name + ".json"), rehearse=False)
    text = json.dumps(objects.cluster(cfg, seed))
    assert hashlib.sha256(text.encode()).hexdigest() == CLUSTERS[name, seed]


# -- the controls ------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("which", sorted(control.EVENT_CONTROLS))
def test_an_event_control_is_not_correct(which, seed):
    total, differ = control.differing_on_events(
        _toy("events-toy"), seed, control.EVENT_CONTROLS[which])
    assert total > 100 and differ > 0


def test_the_event_log_has_every_operation_and_the_reference_agrees_with_itself():
    cfg = _toy("events-toy")
    made = control.event_log(cfg, 11)
    ops = [op for op, _, _ in made["log"]]
    assert {"create", "delete", "node_add", "node_delete"} == set(ops)
    assert ops.index("node_delete") < ops.index("node_add")
    assert control.differing_on_events(cfg, 11, reference.Reference)[1] == 0
    expected = reference.replay(
        reference.Reference(made["nodes"]), made["templates"], made["log"],
        made["may_pend"])
    assert [p for p, n in expected.items() if n is None] == ["large-0"]
    # a control that cannot finish the log has failed on every pod it did
    # not reach: here, all that the log creates behind its first node event

    class Dies(reference.Reference):
        def remove_node(self, name):
            raise reference.Unmodelled("no")

    behind = ops[ops.index("node_delete"):].count("create")
    assert 0 < behind < len(expected)
    assert control.differing_on_events(cfg, 11, Dies) == (
        len(expected), behind)


@pytest.mark.parametrize("seed", SEEDS)
def test_float32_scoring_shows_on_unequal_nodes_of_decimal_sizes(seed):
    """The reading that the five uniform configurations cannot give: nodes of
    four sizes whose memory is a whole multiple of a hundred pods'."""
    total, differ = control.differing(
        _toy("unequal-toy"), seed, control.READINGS["float32"])
    assert total == 500 and differ > 50


# -- the toy, through the harness's own front door ---------------------------

def _rehearse(cell, seed, script=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("BENCH_RUN", "XLA_FLAGS")}
    args = ["--workload", cell, "--seed", str(seed), "--seconds", "1",
            "--trace", "0", "--rehearse", "--bench-dir", TOY_BENCH,
            "--manifest", TOY_MANIFEST]
    cmd = ([sys.executable, os.path.join(BENCH, "run.py")] + args
           if script is None else [sys.executable, script] + args)
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("seed", (7, 3000000019))
@pytest.mark.parametrize("kind", ("host", "device"))
def test_the_toy_with_events_is_correct_against_the_programs_schedulers(
        kind, seed):
    line, out = _rehearse(f"events-toy.{kind}", seed)
    assert line["correct"] is True, line["compared"]
    assert all(c["value"] == c["limit"] == 0
               for c in line["compared"].values())
    assert {"placements_differing", "pods_unbound", "pods_unexpected",
            "nodes_over_allocatable", "removed_node_held_no_pod"} <= set(
                line["compared"])
    # 60 init pods and two passes of 240 pods and the one that cannot fit
    assert "reference replayed 542 pods" in out
    assert "(2 of them expected to stay pending)" in out
    if kind == "device":
        assert "pods on the host path 0," in out


@pytest.mark.parametrize("fault", ("node_delete_swallowed",
                                   "node_add_swallowed",
                                   "pending_pod_bound"))
def test_a_broken_node_event_comes_out_not_correct(fault, tmp_path):
    """The rest of a run with the event lost underneath the program, or the
    pod that cannot fit bound all the same: `correct` reads false."""
    script = tmp_path / "broken.py"
    script.write_text(f"""
import sys
sys.path.insert(0, {ROOT!r}); sys.path.insert(0, {BENCH!r})
from kubernetes_tpu.core.clientset import FakeClientset
import run
fault = {fault!r}
if fault == "node_delete_swallowed":
    FakeClientset.delete_node = lambda self, name: None
elif fault == "node_add_swallowed":
    create = FakeClientset.create_node
    FakeClientset.create_node = lambda self, node: (
        node if "added" in node.name else create(self, node))
else:
    create_pod = FakeClientset.create_pod
    def bound_at_birth(self, pod):
        if pod.name.endswith("-large"):
            pod.node_name = sorted(self.nodes)[0]
        return create_pod(self, pod)
    FakeClientset.create_pod = bound_at_birth
sys.exit(run.main(sys.argv[1:]))
""")
    line, _ = _rehearse("events-toy.host", 5, str(script))
    assert line["correct"] is False
    differing = line["compared"]["placements_differing"]
    assert differing["value"] >= 1 and differing["limit"] == 0
