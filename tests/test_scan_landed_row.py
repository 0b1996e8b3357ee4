"""The scan step's landed row is a mask, not an address (ops/kernel.py `step`,
PR 49): the cases a one-hot form could get wrong and an index form could not.

Two oracles, neither of which shares a line with `step`:

- placements: the host's sequential scheduler with deterministic ties, pod by
  pod (`Scheduler(deterministic_ties=True)`);
- the final `ScanCarry`, field by field: the kernel's own SEEDS of a plan
  built over the cluster as the host left it (a call of zero pods returns
  them): the host's feature build counts the count tables and the mirror
  encodes the resource lanes from the cluster itself, so a carry that the
  step moved row by row must have arrived where a build from nothing starts.
  `ipa_delta`, `start`, `blocked` and `aux_cnt` have no seed that says the
  same (a fresh plan folds them into `ipa_base`, the scheduler's start index,
  the port and attach filters) and are checked from the placements."""

import numpy as np
import pytest

import jax.numpy as jnp

from kubernetes_tpu.core import FakeClientset
from kubernetes_tpu.core.scheduler import Scheduler
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.ops import kernel
from kubernetes_tpu.testing import make_node, make_pod
from tests.test_scan_trip_count import _statics

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
BATCH = 64
SEEDED = ("req_r", "nonzero", "pod_count", "fit_ok", "fit_sc", "ba",
          "dns_counts", "sa_counts", "anti_counts", "aff_counts")


def _nodes(cpus, zones=2, pods=110):
    def fill(cs):
        for i, cpu in enumerate(cpus):
            cs.create_node(make_node().name(f"n{i}").capacity(
                {"cpu": cpu, "memory": "64Gi", "pods": pods})
                .zone(f"z{i % zones}").obj())
    return fill


def _pod(kind, name, cpu="1"):
    b = make_pod().name(name).req({"cpu": cpu, "memory": "128Mi"}).label("app", "t")
    if kind == "spread":          # scan_carried: feasibility + cumsum a step
        b = b.spread_constraint(1, ZONE, "DoNotSchedule", {"app": "t"})
    elif kind == "soft_spread":   # scan_normalised over spread scores
        b = b.spread_constraint(1, ZONE, "ScheduleAnyway", {"app": "t"})
    elif kind == "preferred":     # scan_normalised, incremental feasibility
        b = b.pod_affinity(HOSTNAME, {"app": "t"}, weight=1)
    elif kind == "anti":          # a row-local required anti term
        b = b.pod_affinity(HOSTNAME, {"app": "t"}, anti=True)
    elif kind == "ports":         # port_selfblock
        b = b.host_port(8080)
    else:
        assert kind == "plain", kind
    return b.obj()


def _device(fill, kind, cpu, prepare=None, bound=()):
    """A device scheduler over the cluster (`bound`: pods already on their
    nodes), the plan of a batch of the template, and a call of the kernel
    over it that donates nothing."""
    cs = FakeClientset()
    dev = TPUScheduler(clientset=cs, mesh=None, max_batch=BATCH)
    fill(cs)
    for name, node in bound:
        b = _pod(kind, name, cpu)
        b.node_name = node
        cs.create_pod(b)
    if prepare:
        prepare(dev)
    probe = _pod(kind, "probe", cpu)
    state, plan = dev.build_plan(dev.framework_for_pod(probe), probe, BATCH)
    assert plan.batch_pad == BATCH and not plan.coupling.lap

    def call(n, carry=None):
        return kernel.schedule_batch.__wrapped__(
            state, plan.features, plan.batch_pad, plan.fit_strategy, plan.vmax,
            n_active=jnp.int32(n), carry_in=carry, **_statics(plan))

    rows = [ni.node.name for ni in dev.snapshot.node_info_list]
    return dev, plan, call, rows


def _placed(host, n):
    by_name = {p.name: p.node_name for p in host.clientset.pods.values()}
    return [by_name[f"p{i}"] or None for i in range(n)]


def _check(fill, kind, n, cpu="1", prepare=None, chained_at=None):
    """`n` pods of the template through the kernel against the host's
    placements and the seeds of the cluster the host left; returns the
    placements (node names, None where a pod found no node), the final
    carry and the plan."""
    host = Scheduler(deterministic_ties=True)
    fill(host.clientset)
    if prepare:
        prepare(host)
    for i in range(n):
        host.clientset.create_pod(_pod(kind, f"p{i}", cpu))
    host.run_until_idle()
    want = _placed(host, n)

    dev, plan, call, rows = _device(fill, kind, cpu, prepare)
    if chained_at is None:
        results, carry = call(n)
        results = np.asarray(results)[:, :n]
    else:
        first, carry = call(chained_at)
        second, carry = call(n - chained_at, carry)
        results = np.concatenate([np.asarray(first)[:, :chained_at],
                                  np.asarray(second)[:, :n - chained_at]], axis=1)
    got = [rows[r] if r >= 0 else None for r in results[0]]
    assert got == want, (got, want)

    # the seeds of the cluster as the host left it
    _, after_plan, seeds_of, after_rows = _device(
        fill, kind, cpu, prepare,
        bound=[(f"p{i}", node) for i, node in enumerate(want) if node])
    assert after_rows == rows
    _, seeds = seeds_of(0)
    for field in SEEDED:
        g, w = np.asarray(getattr(carry, field)), np.asarray(getattr(seeds, field))
        assert g.dtype == w.dtype and g.shape == w.shape, field
        assert (g == w).all(), (field, np.argwhere(g != w)[:5])
    # what a fresh plan folds elsewhere, from the placements
    landed = np.zeros(len(np.asarray(carry.pod_count)), np.int64)
    for r in results[0]:
        if r >= 0:
            landed[r] += 1
    if plan.port_selfblock:
        assert (np.asarray(carry.blocked) == (landed > 0)).all()
    else:
        assert not np.asarray(carry.blocked).any()
    if not plan.has_aux:
        assert not np.asarray(carry.aux_cnt).any()
    assert int(carry.start) == int(results[1, -1]) == host.next_start_node_index
    if plan.features.ipa_axis.shape[0]:
        # a preferred hostname term: each landing's weight at its own row, in
        # the carry's table (by the row's hostname value) and in what the
        # fresh plan's base score gained
        weight = landed * int(plan.features.ipa_wland[0])
        gained = (np.asarray(after_plan.features.ipa_base)
                  - np.asarray(plan.features.ipa_base))
        assert (gained == weight).all()
        vid = np.asarray(dev.mirror.flush().topo)[int(plan.features.ipa_axis[0])]
        assert (np.asarray(carry.ipa_delta)[0][vid] * (vid > 0) == weight).all()
    return got, carry, plan


# -- where the landing falls ---------------------------------------------------

@pytest.mark.parametrize("kind", ["plain", "spread", "preferred"])
@pytest.mark.parametrize("big", [0, 4], ids=["row0", "last_valid_row"])
def test_a_landing_on_the_first_and_on_the_last_valid_row(kind, big):
    """Five nodes in 64 rows: the largest node takes the first pod
    (LeastAllocated), at row 0 and at row `num - 1`, whose neighbour is a
    padded row; later pods walk over both ends."""
    cpus = [8] * 5
    cpus[big] = 32
    got, carry, _ = _check(_nodes(cpus, zones=5), kind, 9)
    assert got[0] == f"n{big}"
    assert not np.asarray(carry.pod_count)[5:].any(), "a padded row took a pod"


@pytest.mark.parametrize("kind", ["plain", "spread", "preferred"])
def test_nothing_is_kept_and_no_lane_moves(kind):
    """No node has the room: every step keeps nothing (`best_key` = -1), no
    row is hit, and the carry ends as it began."""
    got, carry, _ = _check(_nodes([2, 2, 2]), kind, 4, cpu="3")
    assert got == [None] * 4
    assert not np.asarray(carry.pod_count).any()


@pytest.mark.parametrize("kind", ["plain", "spread", "soft_spread", "preferred"])
def test_equal_totals_hit_exactly_one_row(kind):
    """Every node ties on every score at every step: the rotation picks, and
    exactly one row takes each pod."""
    n = 13
    got, carry, _ = _check(_nodes([16] * 6, zones=3), kind, n)
    assert None not in got
    assert int(np.asarray(carry.pod_count).sum()) == n


# -- a landing that flips its own row's feasibility ----------------------------

@pytest.mark.parametrize("kind", ["plain", "preferred"])
def test_a_landing_fills_its_row_and_the_walk_goes_on(kind):
    """Nodes of two pods' room: the second landing on a row makes it
    infeasible (the incremental branch patches `okd` and shifts the prefix
    sum), and the pods past the cluster's room find no node."""
    got, carry, _ = _check(_nodes([2, 2, 2]), kind, 8)
    assert got.count(None) == 2 and sorted(x for x in got if x) == sorted(
        ["n0", "n1", "n2"] * 2)
    assert not np.asarray(carry.fit_ok)[:3].any()


@pytest.mark.parametrize("kind", ["plain", "preferred", "spread"])
def test_the_window_walks_over_rows_that_landings_filled(kind):
    """130 nodes of one pod's room, so the sample is cut (100 of 130), the
    start index moves with every pod and every landing empties its row: the
    prefix count is read just before `start` and at the window's end on rows
    that earlier landings made infeasible, and wraps over them."""
    got, carry, _ = _check(_nodes([1] * 130, zones=5), kind, 60)
    assert None not in got and len(set(got)) == 60
    assert int(carry.start) not in (0, 60)


def test_a_port_landing_blocks_its_own_row():
    got, carry, plan = _check(_nodes([8, 8, 8, 8]), "ports", 6)
    assert plan.port_selfblock
    assert got.count(None) == 2 and len(set(got[:4])) == 4
    assert np.asarray(carry.blocked)[:4].all()


def test_a_row_local_anti_term_refuses_its_own_row():
    got, carry, plan = _check(_nodes([8, 8, 8]), "anti", 5)
    assert plan.anti_rowlocal and plan.features.anti_axis.shape[0] == 1
    assert got.count(None) == 2 and len(set(got[:3])) == 3


def _nominate(sched):
    from kubernetes_tpu.core.node_info import PodInfo
    ghost = make_pod().name("ghost").req({"cpu": "3"}).priority(50).obj()
    sched.queue.nominator.add_nominated_pod(PodInfo.of(ghost), "n1")


@pytest.mark.parametrize("kind", ["plain", "preferred"])
def test_the_nominated_lane_counts_in_the_landed_rows_fit(kind):
    """A nominated pod holds 3 of n1's 4 cpus: one landing there fills the
    row in the filter (its score still reads the real pods only)."""
    got, carry, plan = _check(_nodes([4, 4, 4]), kind, 10, prepare=_nominate)
    assert plan.has_nom
    assert got.count("n1") == 1 and got.count("n0") == 4 and got.count(None) == 1


def test_an_attach_limit_counts_the_landings_on_a_row():
    """The counted aux constraint (CSI attach limits, two volumes a node):
    `aux_cnt` moves by the pod's units at the landed row and refuses the row
    at its room. Each pod has a claim of its own, so the host places eight
    pods that differ in nothing else; the kernel, handed the first one's
    plan and eight steps, must land where the host did."""
    from tests.test_volumes import _bound_pvc_pods, _pv_cluster
    cs_h, host = _pv_cluster(Scheduler, n_nodes=3, csi_limit=2)
    ph = _bound_pvc_pods(cs_h, 8, driver="csi.x")
    host.run_until_idle()
    want = [cs_h.bindings.get(p.uid) for p in ph]
    assert sum(1 for v in want if v) == 6

    cs_d, dev = _pv_cluster(TPUScheduler, n_nodes=3, csi_limit=2)
    pd = _bound_pvc_pods(cs_d, 8, driver="csi.x")
    state, plan = dev.build_plan(dev.framework_for_pod(pd[0]), pd[0], BATCH)
    assert plan.has_aux and not plan.coupling.lap
    results, carry = kernel.schedule_batch.__wrapped__(
        state, plan.features, plan.batch_pad, plan.fit_strategy, plan.vmax,
        n_active=jnp.int32(8), carry_in=None, **_statics(plan))
    rows = [ni.node.name for ni in dev.snapshot.node_info_list]
    results = np.asarray(results)
    assert [rows[r] if r >= 0 else None for r in results[0, :8]] == want
    aux = np.asarray(carry.aux_cnt)
    assert (aux[:3] == 2 * int(plan.features.aux_inc)).all() and not aux[3:].any()


# -- chained calls -------------------------------------------------------------

@pytest.mark.parametrize("kind", ["plain", "spread", "soft_spread", "preferred",
                                  "ports"])
def test_two_chained_calls_are_one_call_of_twice_the_pods(kind):
    """`carry_in` from the first call: the second starts where one call of
    all the pods would stand, placements and every field of the carry."""
    fill = _nodes([4, 8, 2, 8, 4, 2], zones=3)
    _, plan, call, _ = _device(fill, kind, "1")
    whole, whole_carry = call(14)
    first, carry = call(7)
    second, carry = call(7, carry)
    whole, first, second = (np.asarray(x) for x in (whole, first, second))
    assert (np.concatenate([first[:, :7], second[:, :7]], axis=1)
            == whole[:, :14]).all()
    for field, g, w in zip(kernel.ScanCarry._fields, carry, whole_carry):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape and (g == w).all(), field
    # and against the host, through the same split
    _check(fill, kind, 14, chained_at=7)
