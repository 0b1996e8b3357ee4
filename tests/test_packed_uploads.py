"""One transfer a payload (`ops/device_state.py` `pack` / `unpack`,
`NodeStateMirror.send` / `upload`, PR 48): a build's features, a flush's dirty
rows, a narrowed plan's state and what a kept plan derives again go to the
device as one packed buffer each and come out of one program as the arrays one
`jnp.asarray` a field gave: leaf for leaf the same value, dtype, shape, weak
type and placement, so every kernel meets the program it met. The resident
state after a packed flush is staging's; the helper is called once a payload
(`scheduler_host_to_device_transfers_total{payload}`); a second round of a
template's build, derive, narrow and flush compiles nothing. No timing is
asserted."""

import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.core.node_info import PodInfo
from kubernetes_tpu.ops import device_state
from kubernetes_tpu.ops.device_state import (DeviceNodeState, NodeStateMirror,
                                             pack, unpack)
from kubernetes_tpu.testing.wrappers import make_node, make_pod

HOSTNAME = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"


def _one_asarray_a_field(self, payload, named):
    """`NodeStateMirror.upload` as the code before PR 48 uploaded: one
    `jnp.asarray` a field, no program."""
    return {name: jnp.asarray(np.asarray(a)) for name, a in named.items()}


PLANES = ("mesh", "one_device")


def _device(plane="mesh", nodes=12):
    """A `TPUScheduler` over `nodes` nodes in three zones, two red pods
    bound on each of the first four; on the mesh of the tests' eight
    virtual devices, or held to one."""
    from kubernetes_tpu.models import TPUScheduler
    sched = TPUScheduler() if plane == "mesh" else TPUScheduler(mesh=None)
    assert (sched.mesh is None) == (plane == "one_device")
    cs = sched.clientset
    for i in range(nodes):
        cs.create_node(make_node().name(f"n{i}").capacity(
            {"cpu": "4", "memory": "32Gi", "pods": 110})
            .zone(f"zone-{i % 3}").label(HOSTNAME, f"n{i}").obj())
    for i in range(4):
        for j in range(2):
            cs.create_pod(make_pod().name(f"init-{i}-{j}").uid(f"init-{i}-{j}")
                          .label("color", "red").node(f"n{i}")
                          .req({"cpu": "500m", "memory": "100Mi"}).obj())
    sched.run_until_idle()
    return sched, cs


def _base(name="probe"):
    return (make_pod().name(name).uid(name).label("color", "red")
            .req({"cpu": "300m", "memory": "200Mi"}))


def _plain(sched):
    return _base().obj(), None


def _hard_spread(sched):
    return _base().spread_constraint(
        1, ZONE, match_labels={"color": "red"}).obj(), None


def _required_anti_affinity(sched):
    return _base().pod_affinity(HOSTNAME, {"color": "red"}, anti=True).obj(), \
        None


def _preferred_affinity(sched):
    return _base().pod_affinity(HOSTNAME, {"color": "red"}, weight=1).obj(), \
        None


def _nominate(sched, name, node):
    held = make_pod().name(name).uid(name).priority(10).req(
        {"cpu": "2", "memory": "1Gi"}).obj()
    held.nominated_node_name = node
    sched.queue.nominator.add_nominated_pod(PodInfo.of(held), node)


def _nominated_lane(sched):
    _nominate(sched, "held", "n5")
    return _base().obj(), None


def _narrowed_one_row(sched):
    _nominate(sched, "held", "n5")
    return _base().obj(), 5


TEMPLATES = (_plain, _hard_spread, _required_anti_affinity,
             _preferred_affinity, _nominated_lane, _narrowed_one_row)


def _same_leaves(got, want, names):
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape, name
        assert a.dtype == b.dtype, name
        assert a.weak_type == b.weak_type, name
        assert a.committed == b.committed, name
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim), name
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


# -- (a) leaf for leaf ---------------------------------------------------------

@pytest.mark.parametrize("plane", PLANES)
@pytest.mark.parametrize("template", TEMPLATES,
                         ids=lambda f: f.__name__.lstrip("_"))
def test_the_packed_features_are_the_uploaded_ones_leaf_for_leaf(
        template, plane, monkeypatch):
    sched, _cs = _device(plane)
    pod, only_row = template(sched)
    fw = sched.framework_for_pod(pod)
    state, plan = sched.build_plan(fw, pod, 8, only_row=only_row)
    with monkeypatch.context() as m:
        m.setattr(NodeStateMirror, "upload", _one_asarray_a_field)
        want_state, want = sched.build_plan(fw, pod, 8, only_row=only_row)
    _same_leaves(plan.features, want.features, plan.features._fields)
    _same_leaves(state, want_state, DeviceNodeState._fields)
    assert plan.has_nom == (template in (_nominated_lane, _narrowed_one_row))
    assert plan.rows == (None if only_row is None else (only_row,))
    # what a kept plan derives again, too (the what-if's and a retry's way)
    sched._sync_mirror()
    _state, kept, _how = sched._preemptor_plan(fw, pod, 8, "dry_run",
                                               only_row=only_row)
    _same_leaves(kept.features, want.features, plan.features._fields)


def test_what_does_not_fit_an_int64_is_refused():
    for odd in (np.zeros(2, np.float32), np.zeros(2, np.uint64),
                np.array(["a"])):
        with pytest.raises(TypeError):
            pack({"odd": odd})


def test_a_packed_buffer_unpacks_to_what_went_in():
    named = {"wide": np.array([[-(1 << 62), 1 << 62]], np.int64),
             "flag": np.array([True, False, True]),
             "scalar": np.int32(-7), "none": np.zeros((0, 7), np.int64),
             "byte": np.array([255], np.uint8)}
    buf, layout = pack(named)
    assert buf.dtype == np.int64 and buf.shape == (2 + 3 + 1 + 0 + 1,)
    got = unpack(jnp.asarray(buf), layout)
    assert list(got) == list(named)
    for name, a in named.items():
        a = np.asarray(a)
        assert got[name].dtype == a.dtype and got[name].shape == a.shape
        assert np.array_equal(np.asarray(got[name]), a), name


# -- (b) the resident state after a packed flush -------------------------------

def _staged(capacity, rng):
    mirror = NodeStateMirror(node_capacity=capacity)
    for a in mirror._arrays() + (mirror.h_topo,):
        a[...] = rng.integers(0, 2 if a.dtype == bool else 1 << 20, a.shape)
    return mirror


@pytest.mark.parametrize("capacity", (8192, 16384))
@pytest.mark.parametrize("count", (1, 25, 2048))
def test_a_packed_flush_leaves_the_device_equal_to_staging(capacity, count):
    rng = np.random.default_rng(capacity + count)
    mirror = _staged(capacity, rng)
    first = mirror.flush()      # the full upload: one array a transfer
    assert mirror.transfers.total() == 0
    dirty = rng.choice(capacity, count, replace=False)
    for a in mirror._arrays():
        a[dirty] = rng.integers(0, 2 if a.dtype == bool else 1 << 40
                                if a.dtype == np.int64 else 1 << 20,
                                a[dirty].shape)
    mirror.h_topo[:, dirty] = rng.integers(0, 1 << 20, (mirror.k_cap, count))
    mirror._dirty.update(int(i) for i in dirty)
    state = mirror.flush()
    assert state is not first
    assert mirror.transfers.value("flush") == 1
    _same_leaves(state, mirror._upload(), DeviceNodeState._fields)


# -- (c) one transfer a payload ------------------------------------------------

def _sent(sched):
    c = sched.metrics.host_to_device_transfers
    return {k: int(c.value(k)) for k in
            ("features", "flush", "rows_state", "derive") if c.value(k)}


def _moved(sched, before):
    after = _sent(sched)
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def test_the_helper_is_called_once_a_payload():
    sched, cs = _device()
    assert sched.mirror.transfers is sched.metrics.host_to_device_transfers
    pod, _ = _plain(sched)
    fw = sched.framework_for_pod(pod)
    before = _sent(sched)
    sched.build_plan(fw, pod, 8)    # its flush is the full upload, or none
    assert _moved(sched, before) == {"features": 1}
    # a pod bound behind the scheduler's back: one dirty row for the flush
    cs.create_pod(make_pod().name("late").uid("late").node("n7")
                  .req({"cpu": "100m"}).obj())
    before = _sent(sched)
    sched.build_plan(fw, pod, 8)
    assert _moved(sched, before) == {"features": 1, "flush": 1}
    # a flush on its own, a state of some rows, a derive with and without
    cs.create_pod(make_pod().name("later").uid("later").node("n8")
                  .req({"cpu": "100m"}).obj())
    sched._sync_mirror()
    before = _sent(sched)
    sched.mirror.flush()
    assert _moved(sched, before) == {"flush": 1}
    before = _sent(sched)
    sched.mirror.rows_state(np.zeros(64, np.int64), 1)
    assert _moved(sched, before) == {"rows_state": 1}
    sched._preemptor_plan(fw, pod, 1, "dry_run")     # builds, and is kept
    for only_row, want in ((None, {"derive": 1}),
                           (3, {"derive": 1, "rows_state": 1})):
        before = _sent(sched)
        _s, _p, how = sched._preemptor_plan(fw, pod, 1, "dry_run",
                                            only_row=only_row)
        assert how == "kept" and _moved(sched, before) == want
    assert "scheduler_host_to_device_transfers_total{payload=\"derive\"}" \
        in sched.metrics.expose()


def test_a_plan_build_says_its_transfers():
    from kubernetes_tpu.testing.annotations import StageAnnotations
    sched, cs = _device()
    sched.stages._annotation = rec = StageAnnotations()
    cs.create_pod(_base("asks-anew").req({"cpu": "350m"}).obj())
    sched.run_until_idle()
    builds = [attrs for name, attrs in rec.opened
              if name == "sched.plan.build"]
    assert builds and builds[-1]["kind"] == "full"
    assert builds[-1]["transfers"] in (1, 2)    # the features, and a flush


# -- (d) a second round compiles nothing ---------------------------------------

def _programs(mirror):
    """Compiled programs of the unpack and of the scatter this mirror's
    flush runs (the mesh plane's is pinned to its shardings)."""
    scatter = (device_state._scatter_rows if mirror._shardings is None
               else device_state._sharded_scatter(mirror._shardings))
    try:
        return device_state._unpacked._cache_size(), scatter._cache_size()
    except AttributeError:  # pragma: no cover - jax internals moved
        pytest.skip("jit cache size introspection unavailable")


@pytest.mark.parametrize("plane", PLANES)
@pytest.mark.parametrize("template", (_plain, _hard_spread, _nominated_lane),
                         ids=lambda f: f.__name__.lstrip("_"))
def test_a_second_round_of_a_template_compiles_nothing(template, plane):
    sched, cs = _device(plane)
    pod, _ = template(sched)
    fw = sched.framework_for_pod(pod)

    def a_round(tag):
        cs.create_pod(make_pod().name(f"late-{tag}").uid(f"late-{tag}")
                      .node("n9").req({"cpu": "100m"}).obj())
        sched._plans.clear()
        sched._sync_mirror()
        sched._preemptor_plan(fw, pod, 1, "dry_run")            # build, flush
        cs.create_pod(make_pod().name(f"later-{tag}").uid(f"later-{tag}")
                      .node("n10").req({"cpu": "100m"}).obj())
        sched._sync_mirror()
        sched._preemptor_plan(fw, pod, 1, "dry_run")            # derive, flush
        sched._preemptor_plan(fw, pod, 8, "nominated", only_row=2)  # narrow

    a_round("first")
    met = _programs(sched.mirror)
    a_round("second")
    assert _programs(sched.mirror) == met, (
        "a payload's layout was met for the first time in the second round: "
        "a compile inside a measured window on the chip")
    assert sched.host_path_pods == 0
