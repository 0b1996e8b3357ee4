"""The benchmark's numpy reference (benchmark/reference.py) pinned to the
program's two schedulers at toy size on the CPU, its controls shown to fail,
and its refusal of what it does not model. No timing is asserted."""

import os
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

CONFIGS = ("spread-5k", "basic-5k")
SEEDS = (7, 3000000019)          # the driver's seeds exceed 32 signed bits


def _config(name):
    return objects.load_config(
        os.path.join(BENCH, "configs", name + ".json"), rehearse=True)


def _scheduler(kind):
    if kind == "host":
        from kubernetes_tpu.core import Scheduler
        return Scheduler(deterministic_ties=True)
    from kubernetes_tpu.models import TPUScheduler
    return TPUScheduler()


@pytest.mark.parametrize("kind", ("host", "device"))
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CONFIGS)
def test_reference_equals_the_programs_schedulers(name, seed, kind):
    """Init pods, then two waves with the restore's deletes between them:
    every placement equal, pod for pod."""
    cfg = _config(name)
    nodes = objects.cluster(cfg, seed)
    sched = _scheduler(kind)
    cs = sched.clientset
    for d in nodes:
        cs.create_node(objects.make_node(d))
    ref = reference.Reference(nodes)
    expected = {}

    def create(group, names):
        proto = objects.make_pod_prototype(cfg[group]["template"])
        pods = [cs.create_pod(objects.stamp(proto, n)) for n in names]
        for n in names:
            expected[n] = ref.schedule(n, cfg[group]["template"])
        sched.run_until_idle()
        return pods

    create("initPods", [f"init-{i}" for i in range(cfg["initPods"]["count"])])
    for w in range(2):
        pods = create("measurePods", [
            f"w{w}-{i}" for i in range(cfg["measurePods"]["count"])])
        got = {p.name: p.node_name for p in cs.pods.values()}
        cmp_ = reference.compare(
            {n: expected[n] for n in got}, got)
        assert (cmp_["differing"], cmp_["unbound"]) == (0, 0), cmp_
        assert ref.over_allocatable() == []
        for p in pods:
            cs.delete_pod(cs.pods[p.uid])
            ref.delete(p.name)
    if kind == "device":
        assert sched.host_path_pods == 0


@pytest.mark.parametrize("which", sorted(control.CONTROLS))
@pytest.mark.parametrize("name", CONFIGS)
def test_a_control_that_breaks_a_guarantee_is_not_correct(name, which):
    total, differ = control.differing(_config(name), 11, control.CONTROLS[which])
    assert total > 0 and differ > 0


@pytest.mark.parametrize("name", CONFIGS)
def test_the_float32_reading_is_what_correct_cannot_see(name):
    """Pinned as found: on these uniform clusters float32 scoring places
    every pod where int64 does, so it is a reading and not a control."""
    total, differ = control.differing(
        _config(name), 11, control.READINGS["float32"])
    assert total > 0 and differ == 0


def test_a_perturbed_score_fails_the_comparison():
    """A node scored one point too high where it is not the reference's
    choice moves placements, and compare() counts them."""
    cfg = _config("basic-5k")

    class Nudged(reference.Reference):
        def scores(self, shape, rows):
            s = super().scores(shape, rows)
            s[-1] += 1
            return s

    total, differ = control.differing(cfg, 5, Nudged)
    assert differ > 0
    cmp_ = reference.compare({"a": "n1", "b": "n2"}, {"a": "n1", "b": "n3"})
    assert cmp_["differing"] == 1 and cmp_["compared"] == 2
    cmp_ = reference.compare({"a": "n1"}, {"a": None, "x": "n9"})
    assert (cmp_["unbound"], cmp_["unexpected"]) == (1, 1)


@pytest.mark.parametrize("template", (
    {"cpu": "100m", "nodeSelector": {"disk": "ssd"}},
    {"cpu": "100m", "podAntiAffinity": {"topologyKey": "kubernetes.io/hostname"}},
    {"cpu": "100m", "labels": {"a": "b"}, "topologySpreadConstraints": [
        {"maxSkew": 1, "whenUnsatisfiable": "ScheduleAnyway"}]},
    {"cpu": "100m", "labels": {"a": "b"}, "topologySpreadConstraints": [
        {"maxSkew": 1, "topologyKey": "kubernetes.io/hostname"}]},
    {"cpu": "100m", "labels": {"a": "b"}, "topologySpreadConstraints": [
        {"maxSkew": 1, "minDomains": 3}]},
))
def test_an_unmodelled_pod_feature_raises(template):
    nodes = reference.node_descriptions(
        {"cpu": 4, "memory": "8Gi", "pods": 10, "zones": 2}, 4, range(4))
    with pytest.raises(reference.Unmodelled):
        reference.Reference(nodes).schedule("p", template)


def test_an_unmodelled_node_feature_raises():
    with pytest.raises(reference.Unmodelled):
        reference.node_descriptions(
            {"cpu": 4, "memory": "8Gi", "pods": 10, "zones": 2,
             "taints": [{"key": "k"}]}, 2, range(2))
    with pytest.raises(reference.Unmodelled):
        reference.node_descriptions(
            {"cpu": 4, "memory": "8Gi", "pods": 10, "zones": 0}, 2, range(2))


def test_no_feasible_node_raises_rather_than_passing():
    nodes = reference.node_descriptions(
        {"cpu": 1, "memory": "1Gi", "pods": 10, "zones": 1}, 1, range(1))
    ref = reference.Reference(nodes)
    ref.schedule("a", {"cpu": "600m"})
    with pytest.raises(reference.Unschedulable):
        ref.schedule("b", {"cpu": "600m"})
