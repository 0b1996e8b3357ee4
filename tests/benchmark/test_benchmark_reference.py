"""The benchmark's numpy reference (benchmark/reference.py and the pod
features under benchmark/reference_features/) pinned to the program's two
schedulers at toy size on the CPU and to the placements recorded before the
features were split out, its controls shown to fail, pod features found by
name, and its refusal of what it does not model. No timing is asserted."""

import hashlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import control  # noqa: E402
import features  # noqa: E402
import objects  # noqa: E402
import reference  # noqa: E402

CONFIGS = ("spread-5k", "basic-5k")
SEEDS = (7, 3000000019)          # the driver's seeds exceed 32 signed bits
TOY_BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "toy_bench")

# Required anti-affinity at toy size, with enough nodes for the adaptive
# sample to matter. The init pods' term names both namespaces; the measured
# pods' term names none, so it selects in their own namespace only and it is
# the init pods' terms (the symmetric half) that keep them off those nodes.
_GREEN = {"labelSelector": {"matchLabels": {"color": "green"}},
          "topologyKey": "kubernetes.io/hostname"}
_REQUIRED = "requiredDuringSchedulingIgnoredDuringExecution"
ANTI = {
    "nodes": {"count": 120, "template": {"cpu": 32, "memory": "256Gi",
                                         "pods": 110, "zones": 50}},
    "initPods": {"count": 30, "template": {
        "cpu": "100m", "memory": "128Mi", "namespace": "sched-0",
        "labels": {"color": "green"}, "podAntiAffinity": {_REQUIRED: [
            dict(_GREEN, namespaces=["sched-1", "sched-0"])]}}},
    "measurePods": {"count": 60, "template": {
        "cpu": "100m", "memory": "128Mi", "namespace": "sched-1",
        "labels": {"color": "green"},
        "podAntiAffinity": {_REQUIRED: [_GREEN]}}},
}
TOY_CONFIGS = {"antiaffinity-toy": ANTI}


def _config(name):
    if name in TOY_CONFIGS:
        return TOY_CONFIGS[name]
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
@pytest.mark.parametrize("name", CONFIGS + tuple(TOY_CONFIGS))
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


# Digests of the reference's placements (init pods, then two waves with the
# deletes between), recorded from the parent's reference.py (commit cc8d4ce)
# before hard spread moved into a feature file: the move changes none.
RECORDED = {
    ("spread-5k", 7): "83b03347af5542cb5b6f3042b6ecd972bb34b0db8435e4b01c8a300ab534fa9c",
    ("spread-5k", 11): "0cdc47bdea9628904bef0b43027dfae1f37cac6032d7b0125edc6d9f5597366e",
    ("spread-5k", 3000000019): "b0eefcce68e42511cd07abfd64c242ed37236557f4296a0e72271a3faebd86ae",
    ("basic-5k", 7): "0e6edfea5c95e776adb1ce6c2fd334888f0c842a48c8bda841ae0585851ad417",
    ("basic-5k", 11): "20e8815e720144b232f7f05730bb7240d3f61ef20d5f50a9978cdd1b5c5dda18",
    ("basic-5k", 3000000019): "6597da1b085702dda6543c0efbfb49491918261abcf02aa81cab2fc17713aa4e",
}


@pytest.mark.parametrize("name,seed", sorted(RECORDED))
def test_placements_are_those_recorded_before_the_split(name, seed):
    cfg = _config(name)
    ref = reference.Reference(objects.cluster(cfg, seed))
    digest = hashlib.sha256()

    def place(group, names):
        for n in names:
            node = ref.schedule(n, cfg[group]["template"])
            digest.update(f"{n}={node}\n".encode())

    place("initPods", [f"init-{i}" for i in range(cfg["initPods"]["count"])])
    for w in range(2):
        names = [f"w{w}-{i}" for i in range(cfg["measurePods"]["count"])]
        place("measurePods", names)
        for n in names:
            ref.delete(n)
    assert digest.hexdigest() == RECORDED[name, seed]


def _controls():
    """The core's controls on the repository's configurations, and on every
    configuration the controls that its pod features state themselves."""
    cases = [(name, which) for name in CONFIGS
             for which in sorted(control.CONTROLS)]
    for name in CONFIGS + tuple(TOY_CONFIGS):
        cases += [(name, which)
                  for which in sorted(control.feature_controls(_config(name)))]
    return cases


@pytest.mark.parametrize("name,which", _controls())
def test_a_control_that_breaks_a_guarantee_is_not_correct(name, which):
    cfg = _config(name)
    broken = {**control.CONTROLS, **control.feature_controls(cfg)}[which]
    total, differ = control.differing(cfg, 11, broken)
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
    {"cpu": "100m", "podAffinity": {_REQUIRED: [_GREEN]}},
    {"cpu": "100m", "podAntiAffinity": {
        "preferredDuringSchedulingIgnoredDuringExecution": [
            {"weight": 1, "podAffinityTerm": _GREEN}]}},
    {"cpu": "100m", "podAntiAffinity": {_REQUIRED: [
        dict(_GREEN, topologyKey="topology.kubernetes.io/zone")]}},
    {"cpu": "100m", "podAntiAffinity": {_REQUIRED: [
        dict(_GREEN, labelSelector={"matchExpressions": [
            {"key": "color", "operator": "In", "values": ["green"]}]})]}},
    {"cpu": "100m", "podAntiAffinity": {_REQUIRED: [
        dict(_GREEN, namespaceSelector={})]}},
    {"cpu": "100m", "namespace": ""},
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


def test_the_builder_refuses_what_the_reference_refuses():
    """A key without feature files is an error on the program's side too: it
    used to be dropped there, and the run then scheduled another pod than
    the reference checked."""
    with pytest.raises(reference.Unmodelled):
        objects.make_pod_prototype({"cpu": "100m", "nodeSelector": {"a": "b"}})


@pytest.mark.parametrize("present", sorted(features.SIDES))
def test_a_feature_with_one_of_its_two_files_fails_at_load(present, tmp_path):
    d = tmp_path / features.SIDES[present]
    d.mkdir()
    (d / "halfThere.py").write_text(
        "def parse(value, template):\n    return value\n"
        "def apply(builder, value, template):\n    return builder\n")
    template = {"cpu": "100m", "halfThere": 1}
    nodes = reference.node_descriptions(
        {"cpu": 4, "memory": "8Gi", "pods": 10, "zones": 2}, 4, range(4))
    with pytest.raises(features.Unpaired):
        reference.Reference(nodes, str(tmp_path)).schedule("p", template)
    with pytest.raises(features.Unpaired):
        objects.make_pod_prototype(template, str(tmp_path))
    # the directory is the caller's to name: unnamed, the key has no file
    with pytest.raises(reference.Unmodelled):
        reference.Reference(nodes).schedule("p", template)


def test_a_features_score_moves_the_chosen_node():
    """The toy feature of tests/benchmark/toy_bench, found in the directory
    the reference is given: its filter keeps a pod inside the required zones, its
    score moves the first maximum to the preferred zone, and a pod without
    the key is placed as before."""
    nodes = reference.node_descriptions(
        {"cpu": 4, "memory": "8Gi", "pods": 10, "zones": 4}, 8, range(8))
    zone = {n["name"]: n["zone"] for n in nodes}
    plain = {"cpu": "100m"}
    required = dict(plain, toyZoneAffinity={"required": ["zone-1", "zone-2"]})
    preferred = dict(plain, toyZoneAffinity={
        "required": ["zone-1", "zone-2"], "preferred": ["zone-2"]})
    ref = reference.Reference(nodes, TOY_BENCH)
    assert zone[ref.schedule("a", plain)] == "zone-0"
    assert zone[ref.schedule("b", required)] == "zone-1"
    # equal resource scores on the two empty candidates: the score decides
    ref = reference.Reference(nodes, TOY_BENCH)
    assert zone[ref.schedule("c", preferred)] == "zone-2"
    assert zone[ref.schedule("d", plain)] == "zone-0"


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
