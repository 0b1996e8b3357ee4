"""The mirror follows a row's pods by column (PR 51). A row whose node is the
one it encoded (`NodeInfo.node_generation`) and whose generation alone moved
has its three dynamic columns written, many rows in one array pass
(`NodeStateMirror._write_pod_columns`), at a sync and at a session's end
(`adopt`, from the live cache); every other row is encoded whole. Held here
to a twin mirror that encodes whole every row whose generation moved: after
any sequence of changes every staging array, the generations, the dirty set,
the census of shapes and the flushed device state are equal, byte for byte.

Scalar slots are handed out in the order names are first met, and the column
pass comes after the walk's whole encodes, so the sequences here never let an
encoded row and a by-column row meet a NEW scalar name in the same sync (in a
cluster a pod's scalar is one its node advertises, met when the node was
encoded): the slots' order is then the twin's."""

import random

import numpy as np
import pytest

from kubernetes_tpu.api.types import Taint
from kubernetes_tpu.core.cache import Cache, Snapshot
from kubernetes_tpu.ops.device_state import NodeStateMirror
from kubernetes_tpu.testing import make_node, make_pod

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
GPU = "example.com/gpu"
SHAPES = (
    {"cpu": "4", "memory": "16Gi", "pods": 110},
    {"cpu": "16", "memory": "64Gi", "pods": 110, GPU: 8},
    {"cpu": "32", "memory": "128Gi", "pods": 64},
)


class WholeRows(NodeStateMirror):
    """The twin: no row is the node it encoded, so every row whose
    generation moved takes `_encode_row`."""

    def _sync_rows(self, node_info_list):
        self._row_node = [-1] * len(self._row_node)
        super()._sync_rows(node_info_list)


def _node(name, shape=SHAPES[0], zone="z0", taints=0):
    b = make_node().name(name).capacity(shape).label(ZONE, zone).label(
        HOST, name)
    for t in range(taints):
        b = b.taint(f"k{t}", f"v{t}")
    return b.obj()


def _pod(name, node, req=None):
    return make_pod().name(name).req(
        req or {"cpu": "100m", "memory": "200Mi"}).node(node).obj()


class Cluster:
    """A cache, its snapshot, the mirror and its twin, kept in step."""

    def __init__(self, nodes=0, axes=(ZONE,)):
        self.cache, self.snapshot = Cache(), Snapshot()
        self.mirror, self.twin = NodeStateMirror(), WholeRows()
        for key in axes:
            self.axis(key)
        self.pods = {}  # name -> Pod, the bound ones
        self.serial = 0
        for i in range(nodes):
            self.cache.add_node(_node(f"n{i}", SHAPES[i % len(SHAPES)],
                                      zone=f"z{i % 3}"))

    def axis(self, key):
        self.mirror.ensure_axis(key)
        self.twin.ensure_axis(key)

    def bind(self, node, req=None):
        self.serial += 1
        pod = _pod(f"p{self.serial}", node, req)
        self.pods[pod.name] = pod
        self.cache.add_pod(pod)
        return pod

    def unbind(self, name):
        self.cache.remove_pod(self.pods.pop(name))

    def sync(self):
        self.cache.update_snapshot(self.snapshot)
        for m in (self.mirror, self.twin):
            m.sync(self.snapshot.node_info_list)

    def holds(self):
        """Staging, generations, dirty rows and census equal the twin's;
        then both flush and the device states are equal too."""
        a, b = self.mirror, self.twin
        assert (a.np_cap, a.t_cap, a.s_cap, a.k_cap) == (
            b.np_cap, b.t_cap, b.s_cap, b.k_cap)
        for x, y in zip(a._arrays() + (a.h_topo,), b._arrays() + (b.h_topo,)):
            assert x.dtype == y.dtype and x.tobytes() == y.tobytes()
        assert a._row_names == b._row_names
        assert a._row_gen == b._row_gen
        assert a._dirty == b._dirty and a._full_flush == b._full_flush
        assert a.shapes == b.shapes and a._row_shape == b._row_shape
        assert a.scalar_slots == b.scalar_slots and a.num_nodes == b.num_nodes
        for x, y in zip(a.flush(), b.flush()):
            assert x.dtype == y.dtype
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
        # and the staging is what a whole encode of every row writes
        assert a._row_node == [ni.node_generation
                               for ni in self.snapshot.node_info_list]

    def step(self):
        self.sync()
        self.holds()

    def counted(self):
        rows = self.mirror.rows
        return {how: int(rows.value(how))
                for how in ("encoded", "by_column", "adopted")}


def _random_walk(seed, nodes, steps):
    rng = random.Random(seed)
    c = Cluster(nodes)
    c.step()
    added = 0
    for _ in range(steps):
        for _ in range(rng.randint(1, 40)):
            names = list(c.cache.nodes)
            op = rng.random()
            if op < 0.45 and names:
                node = rng.choice(names)
                advertised = c.cache.nodes[node].allocatable.scalar_resources
                req = {"cpu": f"{rng.randint(0, 400)}m",
                       "memory": f"{rng.randint(0, 900)}Mi"}
                if advertised and rng.random() < 0.5:
                    req[GPU] = rng.randint(1, 2)
                c.bind(node, req)
            elif op < 0.75 and c.pods:
                c.unbind(rng.choice(list(c.pods)))
            elif op < 0.85 and names:
                # a node update: labels, taints or allocatable; half of them
                # the same Node object mutated in place and handed in again
                name = rng.choice(names)
                old = c.cache.nodes[name].node
                shape = rng.choice(SHAPES)
                fresh = _node(name, shape, zone=f"z{rng.randint(0, 4)}",
                              taints=rng.randint(0, 3))
                if rng.random() < 0.5:
                    old.labels, old.taints = fresh.labels, fresh.taints
                    old.allocatable = fresh.allocatable
                    fresh = old
                c.cache.update_node(fresh)
            elif op < 0.92:
                added += 1
                c.cache.add_node(_node(f"x{added}", rng.choice(SHAPES),
                                       zone=f"z{rng.randint(0, 4)}"))
            elif len(names) > nodes // 2:
                name = rng.choice(names)
                for pod in [p for p in c.pods.values()
                            if p.node_name == name]:
                    c.unbind(pod.name)
                c.cache.remove_node(name)
        if rng.random() < 0.1:
            c.mirror.invalidate()
            c.twin.invalidate()
        c.step()
    return c


@pytest.mark.parametrize("seed,nodes", [(1, 200), (2, 300), (3, 400),
                                        (4, 257), (5, 333)])
def test_a_random_sequence_leaves_the_mirror_equal_to_its_twin(seed, nodes):
    c = _random_walk(seed, nodes, steps=12)
    counted = c.counted()
    # both ways were taken, and the twin took one
    assert counted["encoded"] > nodes and counted["by_column"] > 0
    assert c.twin.rows.value("by_column") == 0
    assert c.twin.rows.value("encoded") == (
        counted["encoded"] + counted["by_column"])


def test_pods_come_and_go_by_column_and_the_counters_say_so():
    c = Cluster(8)
    c.step()
    assert c.counted() == {"encoded": 8, "by_column": 0, "adopted": 0}
    for node in ("n0", "n0", "n3", "n5"):
        c.bind(node)
    c.step()
    assert c.counted() == {"encoded": 8, "by_column": 3, "adopted": 0}
    assert c.mirror.h_pod_count[:8].tolist() == [2, 0, 0, 1, 0, 1, 0, 0]
    c.unbind("p1")
    c.unbind("p4")
    c.step()
    assert c.counted() == {"encoded": 8, "by_column": 5, "adopted": 0}
    assert c.mirror.h_pod_count[:8].tolist() == [1, 0, 0, 1, 0, 0, 0, 0]
    assert not c.mirror.h_req_r[5].any() and not c.mirror.h_nonzero[5].any()
    # nothing moved: nothing is brought in line
    c.step()
    assert c.counted() == {"encoded": 8, "by_column": 5, "adopted": 0}


@pytest.mark.parametrize("what", ["labels", "taints", "allocatable",
                                  "unschedulable"])
@pytest.mark.parametrize("in_place", [False, True],
                         ids=["a_new_object", "mutated_in_place"])
def test_a_node_update_encodes_the_row_whole(what, in_place):
    c = Cluster(6, axes=(ZONE, "rack"))
    c.bind("n2")
    c.step()
    node = c.cache.nodes["n2"].node
    fresh = _node("n2", SHAPES[2], zone="z2")
    changed = {
        # (another zone would move the node in the tree's order)
        "labels": lambda n: n.labels.update({"rack": "r7"}),
        "taints": lambda n: n.taints.append(Taint("dedicated", "db")),
        "allocatable": lambda n: setattr(
            n, "allocatable", _node("n2", SHAPES[0]).allocatable),
        "unschedulable": lambda n: setattr(n, "unschedulable", True),
    }[what]
    changed(node if in_place else fresh)
    c.cache.update_node(node if in_place else fresh)
    c.bind("n4")  # and a row whose pods alone moved, in the same sync
    before = c.counted()
    c.step()
    after = c.counted()
    assert after["encoded"] - before["encoded"] == 1
    assert after["by_column"] - before["by_column"] == 1
    row = c.mirror._row_names.index("n2")
    if what == "unschedulable":
        assert c.mirror.h_unsched[row]
    if what == "taints":
        assert c.mirror.h_taint_key[row].any()
    if what == "labels":
        assert c.mirror.h_topo[c.mirror.axes["rack"].index, row] != 0


def test_nodes_join_and_leave_and_the_tail_shrinks():
    c = Cluster(10)
    for i in range(10):
        c.bind(f"n{i}")
    c.step()
    c.cache.add_node(_node("late", SHAPES[1], zone="z9"))
    c.bind("late", {"cpu": "1", GPU: 1})
    c.bind("n1")
    c.step()
    # a node leaves from the middle: the rows behind it move up
    for pod in [p for p in c.pods.values() if p.node_name == "n4"]:
        c.unbind(pod.name)
    c.cache.remove_node("n4")
    c.bind("n7")
    c.step()
    assert c.mirror.num_nodes == 10
    # a node of one name leaves and comes back: a new node on the same row
    c.cache.remove_node("late")
    c.cache.add_node(_node("late", SHAPES[2], zone="z9"))
    c.step()
    # and half the cluster leaves
    for i in (5, 6, 7, 8, 9):
        for pod in [p for p in c.pods.values() if p.node_name == f"n{i}"]:
            c.unbind(pod.name)
        c.cache.remove_node(f"n{i}")
    c.bind("n0")
    c.step()
    assert c.mirror.num_nodes == 5
    assert not c.mirror.h_valid[5:].any()


def test_a_new_topology_axis_and_a_new_axis_tier_encode_every_row():
    c = Cluster(7)
    c.bind("n1")
    c.step()
    c.bind("n2")
    c.axis(HOST)  # a second axis: every row lacks its column
    before = c.counted()
    c.step()
    assert c.counted()["encoded"] - before["encoded"] == 7
    assert c.counted()["by_column"] == before["by_column"]
    for key in ("a", "b", "c"):  # past k_cap: the staging is made anew
        c.axis(key)
    c.bind("n3")
    c.step()
    assert c.mirror.k_cap == 8
    c.bind("n3")
    before = c.counted()
    c.step()
    assert c.counted()["by_column"] - before["by_column"] == 1


def test_a_taint_tier_that_grows_encodes_every_row_again():
    c = Cluster(9)
    c.bind("n0")
    c.step()
    c.cache.update_node(_node("n6", SHAPES[0], taints=6))  # t_cap is 4
    c.bind("n0")
    c.bind("n8")
    c.step()
    assert c.mirror.t_cap == 8
    c.unbind("p1")
    before = c.counted()
    c.step()
    assert c.counted()["by_column"] - before["by_column"] == 1
    assert c.counted()["encoded"] == before["encoded"]


@pytest.mark.parametrize("names", [1, 6], ids=["one_slot", "past_the_tier"])
def test_a_scalar_resource_first_met_on_one_row_by_column(names):
    c = Cluster(5, axes=())
    c.step()
    # a pod bound from outside, with scalars its node does not advertise:
    # nobody has met the names, and the row's node is the one encoded
    req = {"cpu": "1"}
    req.update({f"example.com/r{j}": j + 1 for j in range(names)})
    c.bind("n0", req)
    c.bind("n3")
    c.step()
    assert len(c.mirror.scalar_slots) == names + 1  # and n1's GPU
    assert c.mirror.s_cap == (4 if names == 1 else 8)
    row = c.mirror._row_names.index("n0")
    slot = c.mirror.scalar_slots["example.com/r0"]
    assert c.mirror.h_req_r[row, slot] == 1
    # the pod leaves: the slot reads 0 again, by column
    c.unbind("p1")
    before = c.counted()
    c.step()
    assert c.mirror.h_req_r[row, slot] == 0
    assert c.counted()["by_column"] - before["by_column"] == 1
    assert c.counted()["encoded"] == before["encoded"]


def test_invalidate_encodes_every_row_and_the_next_sync_is_by_column_again():
    c = Cluster(6)
    c.bind("n1")
    c.step()
    c.mirror.invalidate()
    c.twin.invalidate()
    c.bind("n1")
    before = c.counted()
    c.step()
    assert c.counted()["encoded"] - before["encoded"] == 6
    c.bind("n1")
    before = c.counted()
    c.step()
    assert c.counted()["by_column"] - before["by_column"] == 1
    assert c.counted()["encoded"] == before["encoded"]


def test_a_what_if_on_the_snapshots_clone_comes_back_by_column():
    """`Snapshot.assume_pod` / `forget_pod` (the gang simulation) move a
    CLONE's generation past its live node's with the same content."""
    c = Cluster(4)
    c.bind("n1")
    c.step()
    pod = _pod("what-if", "n1")
    c.snapshot.assume_pod(pod)
    c.snapshot.forget_pod(pod)
    for m in (c.mirror, c.twin):
        m.sync(c.snapshot.node_info_list)
    c.holds()
    c.bind("n1")
    c.step()


def _adopting_pair(nodes=12):
    """Two mirrors synced and flushed to one snapshot, and their cluster."""
    c = Cluster(nodes)
    c.twin = NodeStateMirror()
    c.twin.ensure_axis(ZONE)
    for i in range(nodes):
        c.bind(f"n{i}")
    c.sync()
    for m in (c.mirror, c.twin):
        m.flush()
    return c


def _carry(m):
    device = m._device
    return device.req_r, device.nonzero, device.pod_count


def test_adopt_from_the_live_cache_equals_adopt_from_a_refreshed_snapshot():
    c = _adopting_pair()
    landed = [1, 1, 4, 7, 7, 7, 11]  # a row a pod, as a session's ok_rows
    for row in landed:
        c.bind(f"n{row}", {"cpu": "250m", "memory": "1Gi"})
    c.bind("n9", {"cpu": "1", GPU: 1})  # n9 does not advertise it
    landed.append(9)
    assert c.mirror.adopt(c.cache.nodes, landed, *_carry(c.mirror)) == 5
    c.cache.update_snapshot(c.snapshot)  # the twin's way: refreshed first
    assert c.twin.adopt(c.snapshot.node_info_map, landed,
                        *_carry(c.twin)) == 5
    a, b = c.mirror, c.twin
    for x, y in zip(a._arrays() + (a.h_topo,), b._arrays() + (b.h_topo,)):
        assert x.tobytes() == y.tobytes()
    assert a._row_gen == b._row_gen and a._row_node == b._row_node
    assert not a._dirty and not b._dirty
    assert a.h_pod_count[:12].tolist() == [1, 3, 1, 1, 2, 1, 1, 4, 1, 2, 1, 2]
    assert c.counted() == {"encoded": 12, "by_column": 0, "adopted": 5}
    # the clones made since carry the generations adopt took: the next sync
    # brings no row in line, and a flush has nothing to send
    for m in (a, b):
        m.sync(c.snapshot.node_info_list)
        assert not m._dirty
    assert c.counted() == {"encoded": 12, "by_column": 0, "adopted": 5}
    # and a row that moves afterwards is seen
    c.unbind("p1")
    c.sync()
    assert c.counted() == {"encoded": 12, "by_column": 1, "adopted": 5}
    assert a._dirty == {0}


def test_adopt_leaves_a_row_whose_node_changed_to_the_next_sync():
    c = _adopting_pair(6)
    c.bind("n2")
    c.bind("n3")
    c.cache.update_node(_node("n3", SHAPES[2], zone="z0", taints=1))
    dirty_before = set(c.mirror._dirty)
    assert c.mirror.adopt(c.cache.nodes, [2, 3, 40], *_carry(c.mirror)) == 1
    assert c.mirror._dirty == dirty_before
    assert c.mirror.h_pod_count[:6].tolist() == [1, 1, 2, 1, 1, 1]
    before = c.counted()
    c.cache.update_snapshot(c.snapshot)
    c.mirror.sync(c.snapshot.node_info_list)  # n3 whole, n2 not at all
    assert c.counted()["encoded"] - before["encoded"] == 1
    assert c.counted()["by_column"] == before["by_column"]
    assert c.mirror._dirty == dirty_before | {3}
    whole = WholeRows()
    whole.ensure_axis(ZONE)
    whole.sync(c.snapshot.node_info_list)
    for x, y in zip(c.mirror._arrays() + (c.mirror.h_topo,),
                    whole._arrays() + (whole.h_topo,)):
        assert x.tobytes() == y.tobytes()


def test_adopt_with_a_full_upload_pending_touches_nothing():
    c = _adopting_pair(4)
    c.bind("n1")
    c.mirror.invalidate()
    staged = c.mirror.h_pod_count.copy()
    assert c.mirror.adopt(c.cache.nodes, [1], *_carry(c.mirror)) == 0
    assert (c.mirror.h_pod_count == staged).all()
    assert c.counted()["adopted"] == 0
