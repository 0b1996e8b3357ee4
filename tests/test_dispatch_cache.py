"""Warm/live dispatch trace identity.

warm_for exists to put XLA compilation OUTSIDE measured windows; that only
works if every live dispatch is call-signature-identical to the warm ones
(static kwargs are part of jit's cache-key pytree structure — an omitted-vs-
explicit kwarg is a different structure and retraces). Round 2's headline
"regression" (TopologySpreading at 0.22x baseline) was exactly such a
mismatch: a ~1min compile inside every measured window. These tests pin the
invariant with jit's trace-cache size so it can never silently return.
"""

import pytest

from kubernetes_tpu.core import FakeClientset
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.ops.kernel import schedule_batch
from kubernetes_tpu.testing import make_node, make_pod

ZONE = "topology.kubernetes.io/zone"


def _cache_size():
    try:
        return schedule_batch._cache_size()
    except AttributeError:  # pragma: no cover - jax internals moved
        pytest.skip("jit cache size introspection unavailable")


def _cluster(n_nodes=40):
    cs = FakeClientset()
    s = TPUScheduler(clientset=cs)
    for i in range(n_nodes):
        cs.create_node(
            make_node().name(f"n{i}")
            .capacity({"cpu": 16, "memory": "64Gi", "pods": 110})
            .zone(f"z{i % 4}").obj())
    return cs, s


@pytest.mark.parametrize("template", ["basic", "spread", "anti"])
def test_no_retrace_after_warm(template):
    cs, s = _cluster()

    def pod(name):
        b = make_pod().name(name).req({"cpu": "100m"})
        if template == "spread":
            b = b.label("app", "t").spread_constraint(
                1, ZONE, "DoNotSchedule", {"app": "t"})
        elif template == "anti":
            b = b.label("app", "t").pod_affinity(
                "kubernetes.io/hostname", {"app": "t"}, anti=True)
        return b.obj()

    s.warm_for(pod("warm-template"))
    warmed = _cache_size()
    # Enough pods for two chained batches: exercises the fresh-carry AND
    # chained-carry live dispatches.
    for i in range(30):
        cs.create_pod(pod(f"p{i}"))
    s.run_until_idle()
    assert s.scheduled == 30 and s.host_path_pods == 0
    assert _cache_size() == warmed, (
        "live dispatch retraced schedule_batch after warm_for — a compile "
        "would land inside the measured window on real hardware")


def _flip_shared_hostname(cs):
    """A node that shares another's hostname value: the anti axis is no
    longer one node a value, so anti_rowlocal reads False."""
    cs.create_node(
        make_node().name("n-dup").label("kubernetes.io/hostname", "n0")
        .capacity({"cpu": 16, "memory": "64Gi", "pods": 110})
        .zone("z0").obj())


def _flip_existing_anti(cs):
    """A bound pod whose required anti-affinity term matches the measured
    pods: exist_anti goes nonzero, the plan is no longer pod_local."""
    cs.create_pod(
        make_pod().name("guard").node("n1").req({"cpu": "100m"})
        .pod_affinity("kubernetes.io/hostname", {"app": "t"}, anti=True)
        .obj())


@pytest.mark.parametrize("flip", [_flip_shared_hostname, _flip_existing_anti])
def test_no_retrace_when_a_plan_leaves_the_engine_it_was_warmed_on(flip):
    """warm_for also warms the program a mid-workload flip of the coupling
    facts lands on: the anti-affinity plan's scan fallback (anti_rowlocal
    turns False) and, under a mesh, the row-local plan's GSPMD
    schedule_batch (the plan stops being row-local)."""
    cs, s = _cluster()
    assert s.mesh is not None

    def pod(name):
        b = make_pod().name(name).req({"cpu": "100m"}).label("app", "t")
        if flip is _flip_shared_hostname:
            b = b.pod_affinity("kubernetes.io/hostname", {"app": "t"},
                               anti=True)
        return b.obj()

    fw = next(iter(s.profiles.values()))
    _state, before = s.build_plan(fw, pod("probe"), s.max_batch)
    assert before.rides_lap
    assert (s._shard_map_fn(before) is not None) == (
        flip is _flip_existing_anti)
    s.warm_for(pod("warm-template"))
    warmed = _cache_size()
    flip(cs)
    _state, after = s.build_plan(fw, pod("probe"), s.max_batch)
    assert (before.anti_rowlocal, before.pod_local) != (
        after.anti_rowlocal, after.pod_local)
    assert s._shard_map_fn(after) is None
    for i in range(30):
        cs.create_pod(pod(f"p{i}"))
    s.run_until_idle()
    assert s.scheduled == 30 and s.host_path_pods == 0
    assert s.shard_map_dispatches == 0
    assert _cache_size() == warmed, (
        "the flipped plan retraced schedule_batch: warm_for did not warm "
        "the program it falls back to")


def test_a_preferred_term_is_one_program_on_an_empty_and_a_loaded_cluster():
    """A pod with a preferred inter-pod term that selects itself has a
    landing axis, and with one the kernel reads `ipa_base` whether or not any
    entry is nonzero. The plan over an empty cluster (all-zero base) and the
    plan over a loaded one are then the same compiled program: warming on
    the empty cluster covers the workload (on the chip each variant of the
    scan is 2.5-3.8 s to load even from the cache: benchmark
    prefaffinity-5k, PR 31)."""
    cs, s = _cluster()

    def pod(name):
        return (make_pod().name(name).req({"cpu": "100m"}).label("app", "t")
                .pod_affinity("kubernetes.io/hostname", {"app": "t"},
                              weight=1).obj())

    fw = next(iter(s.profiles.values()))
    _state, empty = s.build_plan(fw, pod("probe"), s.max_batch)
    assert empty.has_ipa_base and empty.engine == "scan_normalised"
    s.warm_for(pod("warm-template"))
    warmed = _cache_size()
    for wave in range(2):
        for i in range(30):
            cs.create_pod(pod(f"w{wave}-{i}"))
        s.run_until_idle()
    _state, loaded = s.build_plan(fw, pod("probe"), s.max_batch)
    assert loaded.has_ipa_base and bool((loaded.features.ipa_base != 0).any())
    assert s.scheduled == 60 and s.host_path_pods == 0
    assert _cache_size() == warmed, (
        "the loaded cluster's plan retraced schedule_batch: a second program "
        "for a flag that changes nothing in it")
    # a plain pod among pods that pull it has a base and no axis: the flag
    # still says so, and its program is another one
    plain = make_pod().name("plain").req({"cpu": "100m"}).label("app", "t").obj()
    _state, pulled = s.build_plan(fw, plain, s.max_batch)
    assert pulled.has_ipa_base and pulled.features.ipa_axis.shape[0] == 0


# What one landing reaches, by plan shape: ops/kernel.py `coupling` as the
# host reads it (BatchPlan.rides_lap / .row_local), and what follows from
# it: the score-hint walk (hint_eligible) and, under the mesh, the explicit
# shard_map lap (_shard_map_fn). The table is the contract; a new lane adds
# a row.
#   shape            (rides_lap, row_local, hint_eligible, shard_map)
_ENGINES = {
    "fit_only":      (True,  True,  True,  True),
    "small_batch":   (False, True,  True,  False),
    "hostname_anti": (True,  False, False, False),
    "hard_spread":   (False, False, False, False),
    "na_preferred":  (False, False, False, False),
    "host_port":     (True,  False, False, False),
    "nominated":     (True,  False, False, False),
}


@pytest.mark.parametrize("shape", list(_ENGINES))
def test_one_predicate_decides_the_engine(shape):
    from kubernetes_tpu.core.node_info import PodInfo
    from kubernetes_tpu.models.score_hints import hint_eligible
    from kubernetes_tpu.ops.kernel import SCAN_MAX_BATCH

    cs, s = _cluster()
    assert s.mesh is not None  # tests/conftest.py: 8 virtual devices
    b = make_pod().name("probe").req({"cpu": "100m"}).label("app", "t")
    if shape == "hostname_anti":
        b = b.pod_affinity("kubernetes.io/hostname", {"app": "t"}, anti=True)
    elif shape == "hard_spread":
        b = b.spread_constraint(1, ZONE, "DoNotSchedule", {"app": "t"})
    elif shape == "na_preferred":
        b = b.preferred_node_affinity(10, ZONE, ["z1"])
    elif shape == "host_port":
        b = b.host_port(8080)
    elif shape == "nominated":
        s.queue.nominator.add_nominated_pod(
            PodInfo.of(make_pod().name("nominee").priority(10)
                       .req({"cpu": "1"}).obj()), "n3")
    pod = b.obj()
    batch = SCAN_MAX_BATCH if shape == "small_batch" else s.max_batch
    fw = s.framework_for_pod(pod)
    _state, plan = s.build_plan(fw, pod, batch)
    got = (plan.rides_lap, plan.row_local,
           hint_eligible(plan, (None, None), pod, s.extenders,
                         s.queue.nominator, s.cache.affinity_pod_refs),
           s._shard_map_fn(plan) is not None)
    assert got == _ENGINES[shape]


@pytest.mark.parametrize("n_active", [0, 5, 1024])
def test_one_plan_meets_one_scan_program_whatever_it_holds(n_active):
    """The scan's trip count is the device scalar `n_active`, an input and
    not a static argument: an empty dispatch (warm_for's), a served one of a
    few pods and a full batch run the one pair of programs (fresh and
    chained carry) that warm_for met."""
    cs, s = _cluster()
    pod = (make_pod().name("probe").req({"cpu": "100m"}).label("app", "t")
           .spread_constraint(1, ZONE, "DoNotSchedule", {"app": "t"}).obj())
    state, plan = s.build_plan(s.framework_for_pod(pod), pod, s.max_batch)
    assert plan.engine == "scan_carried" and plan.batch_pad == 1024
    s.warm_for(pod)
    warmed = _cache_size()
    results, carry = s._dispatch(state, plan, n_active, None)
    chained, _carry = s._dispatch(state, plan, n_active, carry)
    assert results.shape == chained.shape == (2, 1024)
    assert _cache_size() == warmed
