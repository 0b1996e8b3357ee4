"""`HintEntry._reval_rows` (the array form a session's end uses, PR 41) gives
`_reval_row`'s integers row for row: fit verdict, fit score, balanced
allocation, total and `ok`, on entries made up here without a scheduler.
Every case holds the rows that take a branch of their own: no allocatable in
a fit slot, none at all, more used than allocatable, a full node, a blocked
row, a row the static verdicts refuse."""

import copy
import itertools

import numpy as np
import pytest

from kubernetes_tpu.models.score_hints import HintEntry

ROWS, LANES, SCALAR = 96, 7, 4          # a scalar resource rides lane 4
GI = 1 << 30

LANE_FIELDS = ("fit_ok", "fit_sc", "ba", "total", "ok")


def _entry(seed, strategy, slots, ba_skip, has_request, fit_enabled):
    rng = np.random.default_rng(seed)
    e = HintEntry()
    e.node_names = [f"node-{i}" for i in range(ROWS - 8)]   # 8 padding rows
    e.NP, e.num = ROWS, len(e.node_names)
    e.fit_strategy = strategy
    e.fit_slots = np.asarray(slots, np.int64)
    e.fit_weights = rng.integers(1, 4, len(slots)).astype(np.int64)
    e.ba_skip, e.has_request = ba_skip, has_request
    e.enable = (1, 1, 1, 1, fit_enabled)
    e.w_tt, e.w_fit, e.w_ba, e.w_il = 3, 1, 1, 1
    e.request = np.zeros(LANES, np.int64)
    e.request[0], e.request[1] = 250, GI // 2
    if SCALAR in slots or seed % 2:
        e.request[SCALAR] = 2
    e.nz_request = e.request[:2].copy()
    e.alloc_r = np.zeros((ROWS, LANES), np.int64)
    e.alloc_r[:, 0] = rng.choice([2000, 4000, 64000], ROWS)
    e.alloc_r[:, 1] = rng.choice([8, 32, 256], ROWS) * GI
    e.alloc_r[:, 2] = 100 * GI
    e.alloc_r[:, SCALAR] = rng.integers(0, 9, ROWS)
    e.alloc_pods = rng.choice([4, 110], ROWS).astype(np.int64)
    # anywhere from empty to a fifth over what the node holds
    e.req_r = (e.alloc_r * rng.integers(0, 120, (ROWS, LANES))) // 100
    e.nonzero = e.req_r[:, :2] + rng.integers(0, 3, (ROWS, 2)) * 100
    e.pod_count = rng.integers(0, 5, ROWS).astype(np.int64)
    e.il_score = rng.integers(0, 101, ROWS).astype(np.int64)
    e.static_ok = rng.random(ROWS) > 0.1
    e.blocked = rng.random(ROWS) > 0.9
    # the rows with a branch of their own
    e.alloc_r[0, 0] = 0                         # no cpu: a fit slot drops out
    e.alloc_r[1, 1] = 0                         # no memory
    e.alloc_r[2] = 0                            # nothing allocatable at all
    e.req_r[3] = e.alloc_r[3] * 2               # used > allocatable
    e.nonzero[3] = e.req_r[3, :2]
    e.blocked[4], e.static_ok[4] = True, True
    e.pod_count[5] = e.alloc_pods[5]            # at its pod limit
    e.pod_count[6] = e.alloc_pods[6] - 1        # one short of it
    e.static_ok[7], e.blocked[7] = False, False
    e.alloc_r[8, SCALAR] = 0                    # no scalar resource
    e.req_r[9], e.nonzero[9] = 0, 0             # an empty node
    for row in (10, 11):                        # fits to the unit
        e.alloc_r[row, SCALAR], e.pod_count[row] = 8, 0
        e.req_r[row] = e.alloc_r[row] - e.request
        e.nonzero[row] = e.req_r[row, :2]
    e.req_r[11, 0] += 1                         # one milli short
    e.fit_ok, e.ok = np.zeros(ROWS, bool), np.zeros(ROWS, bool)
    e.fit_sc, e.ba, e.total = (np.full(ROWS, -7, np.int64) for _ in range(3))
    e._pending = []
    return e


CASES = [
    pytest.param(strategy, slots, ba_skip, has_request, fit_enabled,
                 id=f"{'Least' if strategy == 0 else 'Most'}Allocated-"
                    f"slots{'.'.join(map(str, slots))}-ba_skip{ba_skip}-"
                    f"has_request{has_request}-fit{fit_enabled}")
    for strategy, slots, (ba_skip, has_request, fit_enabled) in
    itertools.product(
        (0, 1), ((0,), (0, 1), (0, 1, SCALAR)),
        ((0, 1, 1), (1, 1, 1), (0, 0, 1), (0, 1, 0)))
]


@pytest.mark.parametrize(
    "strategy,slots,ba_skip,has_request,fit_enabled", CASES)
def test_the_array_form_is_the_scalar_form_row_for_row(
        strategy, slots, ba_skip, has_request, fit_enabled):
    for seed in (1, 2, 3):
        scalar = _entry(seed, strategy, slots, ba_skip, has_request,
                        fit_enabled)
        array = copy.deepcopy(scalar)
        for row in range(scalar.num):
            scalar._reval_row(row)
        array._reval_rows(slice(0, array.num))
        for name in LANE_FIELDS:
            want, got = getattr(scalar, name), getattr(array, name)
            assert got.dtype == want.dtype, name
            differ = np.flatnonzero(got != want)
            assert not differ.size, (name, seed, differ[:8],
                                     want[differ[:8]], got[differ[:8]])
        # the rows with a branch of their own took it
        assert not scalar.ok[4] and not scalar.ok[7]
        if fit_enabled:
            assert not scalar.fit_ok[5] and scalar.fit_ok[10]
            assert scalar.fit_ok[11] == (has_request == 0)
        else:
            assert scalar.fit_ok[:scalar.num].all()
        if ba_skip:
            assert not scalar.ba[:scalar.num].any()
        else:
            assert scalar.ba[2] == 100          # nothing allocatable: the top
        assert scalar.fit_sc[2] == 0 and scalar.fit_sc[:scalar.num].any()


def test_a_run_of_rows_leaves_the_other_rows_alone():
    e = _entry(5, 0, (0, 1), 0, 1, 1)
    e._reval_rows(slice(0, 40))
    for name in LANE_FIELDS:
        lane = getattr(e, name)
        assert (lane[40:] == (0 if lane.dtype == bool else -7)).all(), name
    whole = copy.deepcopy(e)
    whole._reval_rows(slice(0, whole.num))
    for name in LANE_FIELDS:
        assert (getattr(whole, name)[:40] == getattr(e, name)[:40]).all()
        assert (getattr(whole, name)[whole.num:]
                == (0 if name in ("fit_ok", "ok") else -7)).all(), name


def _sibling_and_fresh():
    sibling = _entry(7, 0, (0, 1), 0, 1, 1)
    sibling._reval_rows(slice(0, sibling.num))
    fresh = _entry(8, 0, (0, 1), 0, 1, 1)       # another session's carry
    return sibling, fresh


def test_resync_takes_the_carrys_pod_state_and_keeps_what_is_the_entrys_own():
    sibling, fresh = _sibling_and_fresh()
    before = copy.deepcopy(sibling)
    sibling._pending = [(1, 2, 3)]
    assert sibling.resync_rows(fresh) is True
    n = sibling.num
    for name in ("req_r", "nonzero", "pod_count"):
        assert (getattr(sibling, name)[:n] == getattr(fresh, name)[:n]).all()
        # the rows behind the cluster's last are nobody's
        assert (getattr(sibling, name)[n:] == getattr(before, name)[n:]).all()
        assert getattr(sibling, name) is not getattr(fresh, name)
    for name in ("alloc_r", "alloc_pods", "static_ok", "blocked"):
        assert (getattr(sibling, name) == getattr(before, name)).all(), name
    assert sibling._pending == []
    # what the scalar pass over the same state gives
    want = copy.deepcopy(sibling)
    for row in range(n):
        want._reval_row(row)
    for name in LANE_FIELDS:
        assert (getattr(sibling, name) == getattr(want, name)).all(), name
    assert not sibling.ok[4], "a blocked row came back"
    # the carry is copied, not shared: the fresh entry's walk moves only its own
    fresh.req_r[0, 0] += 1
    assert sibling.req_r[0, 0] != fresh.req_r[0, 0]


@pytest.mark.parametrize("what", ["node_added", "node_removed",
                                  "node_renamed", "lanes_grew"])
def test_resync_refuses_rows_that_are_not_the_sessions(what):
    sibling, fresh = _sibling_and_fresh()
    if what == "node_added":
        fresh.node_names = fresh.node_names + ["late"]
    elif what == "node_removed":
        fresh.node_names = fresh.node_names[:-1]
    elif what == "node_renamed":
        fresh.node_names = fresh.node_names[:-1] + ["other"]
    else:
        fresh.req_r = np.zeros((ROWS, LANES + 4), np.int64)
    before = copy.deepcopy(sibling)
    assert sibling.resync_rows(fresh) is False
    for name in ("req_r", "nonzero", "pod_count") + LANE_FIELDS:
        assert (getattr(sibling, name) == getattr(before, name)).all(), name
