"""The kernels' quotients without `//`: `_bounded_divmod` and the wraps of
ops/kernel.py against Python's `//` and `%` over each call site's domain,
then `_resource_eval` whole against plugins/noderesources.py on nodes of
unequal allocatable and pods of odd requests. The benchmark's `correct`
cannot see a scoring error on its uniform clusters (PERF.md §2): this is
where exactness is proved."""

import random

import numpy as np
import pytest

import jax.numpy as jnp

from kubernetes_tpu.ops.features import _pow2
from kubernetes_tpu.ops.kernel import (
    LAP_MAX,
    MAX_NODE_SCORE,
    _LAP_BITS,
    _SCALE,
    _SCALE_BITS,
    _SCORE_BITS,
    _bounded_divmod,
    _normalize_default_reverse,
    _resource_eval,
    _unwrap,
    _wrap,
)

GI = 1 << 30
# Allocatable sizes the sites see: cpu in millicores, memory at 2**38..2**41.
ALLOCS = [1, 2, 3, 7, 100, 999, 1000, 4000, 31_999, 32_000, 96_001, 256_000,
          (1 << 38) - 1, 1 << 38, (1 << 38) + 1, 256 * GI, 3 * (1 << 39) + 12345,
          (1 << 41) - 1, 1 << 41]


def _useds(alloc, rng):
    edge = {0, 1, alloc - 1, alloc, alloc + 1, alloc // 2, alloc // 3,
            alloc // 100, alloc // 100 + 1, 2 * alloc}
    edge |= {rng.randrange(0, alloc + 1) for _ in range(40)}
    return sorted(u for u in edge if u >= 0)


def _least(rng):
    for a in ALLOCS:
        for u in _useds(a, rng):
            yield (a - u) * 100 if u <= a else 0, a


def _most(rng):
    for a in ALLOCS:
        for u in _useds(a, rng):
            yield min(u, a) * 100, a


def _fit_avg(rng):
    weights = [1, 2, 3, 50, 99, 100]
    for _ in range(600):
        ws = [rng.choice(weights) for _ in range(rng.choice((1, 2, 3)))]
        yield sum(rng.randrange(0, 101) * w for w in ws), sum(ws)
    yield 0, 1
    yield 100 * 100, 100
    yield 100 * 300, 300
    yield 99 * 300 + 299, 300


def _millionths(rng):
    # the guard `used >= alloc` took the saturated side: used < alloc here
    for a in ALLOCS:
        for u in _useds(a, rng):
            if u < a:
                yield u * _SCALE, a


def _ba_step(rng):
    diffs = {0, 1, 2, 19_999, 20_000, 20_001, 499_999, 500_000, _SCALE - 1, _SCALE}
    diffs |= {rng.randrange(0, _SCALE + 1) for _ in range(500)}
    for d in sorted(diffs):
        yield MAX_NODE_SCORE * _SCALE - 50 * d, _SCALE


def _normalise(rng):
    # 100 * raw // mx with 0 <= raw <= mx: reverse, pts, ipa and na on kept rows
    for mx in (1, 2, 3, 7, 99, 100, 101, 1024, 12_345, 5_000 * 1024 * 14, 1 << 40):
        raws = {0, 1, mx - 1, mx, mx // 2, mx // 3, mx // 100, mx // 100 + 1}
        raws |= {rng.randrange(0, mx + 1) for _ in range(60)}
        for raw in sorted(r for r in raws if 0 <= r <= mx):
            yield 100 * raw, mx


def _lap_window(rng):
    # (rank - 1) divmod to_find; windows below 2**_LAP_BITS stay exact
    for tf in (1, 2, 3, 7, 100, 499, 500, 5000):
        top = tf * (1 << _LAP_BITS) - 1
        ranks = {0, 1, tf - 1, tf, tf + 1, LAP_MAX * tf - 1, LAP_MAX * tf,
                 LAP_MAX * tf + 1, top}
        ranks |= {rng.randrange(0, top + 1) for _ in range(60)}
        for n in sorted(ranks):
            yield n, tf


SITES = {
    "least_allocated": (_least, _SCORE_BITS, np.int64),
    "most_allocated": (_most, _SCORE_BITS, np.int64),
    "fit_num_over_fit_den": (_fit_avg, _SCORE_BITS, np.int64),
    "used_millionths": (_millionths, _SCALE_BITS, np.int64),
    "ba_constant_divisor": (_ba_step, _SCORE_BITS, np.int64),
    "kept_set_normalisations": (_normalise, _SCORE_BITS, np.int64),
    "lap_windows": (_lap_window, _LAP_BITS, np.int32),
}


@pytest.mark.parametrize("site", sorted(SITES))
def test_bounded_divmod_is_floor_division_on_the_sites_domain(site):
    gen, bits, dtype = SITES[site]
    pairs = list(gen(random.Random(f"division-{site}")))
    assert len(pairs) > 50
    n = np.array([p[0] for p in pairs], dtype)
    d = np.array([p[1] for p in pairs], dtype)
    want_q = [a // b for a, b in pairs]
    want_r = [a % b for a, b in pairs]
    assert max(want_q) < 1 << bits, "the generator left the site's domain"
    q, r = _bounded_divmod(jnp.asarray(n), jnp.asarray(d), bits)
    assert q.dtype == n.dtype and r.dtype == n.dtype
    assert np.asarray(q).tolist() == want_q
    assert np.asarray(r).tolist() == want_r
    if site == "ba_constant_divisor":
        assert min(want_q) == 50 and max(want_q) == 100
    if site in ("least_allocated", "most_allocated", "kept_set_normalisations"):
        assert max(want_q) == 100 and min(want_q) == 0


@pytest.mark.parametrize("bits", [_SCORE_BITS, _SCALE_BITS, _LAP_BITS])
def test_out_of_domain_inputs_are_defined_and_leave_the_rest_alone(bits):
    """Rows outside the kept set hand the helper negative numerators and
    quotients past the bound: it saturates or returns 0, raises nothing,
    and the in-domain lanes beside them read what they read alone."""
    top = (1 << bits) - 1
    d = 12_345
    good = [(0, d), (d - 1, d), (d, d), (top * d, d), (top * d + d - 1, d)]
    bad = [(-1, d), (-(1 << 50), d), ((top + 1) * d, d), ((1 << 50), 3),
           (5, 1 << 62), (-7, 1 << 62)]
    n = jnp.asarray(np.array([p[0] for p in good + bad], np.int64))
    dd = jnp.asarray(np.array([p[1] for p in good + bad], np.int64))
    q, r = _bounded_divmod(n, dd, bits)
    q, r = np.asarray(q).tolist(), np.asarray(r).tolist()
    k = len(good)
    assert q[:k] == [a // b for a, b in good]
    assert r[:k] == [a % b for a, b in good]
    alone = _bounded_divmod(n[:k], dd[:k], bits)
    assert np.asarray(alone[0]).tolist() == q[:k]
    assert q[k] == 0 and r[k] == -1                 # n < 0: (0, n)
    assert q[k + 1] == 0
    assert q[k + 2] == top and r[k + 2] >= d        # saturated, remainder says so
    assert q[k + 3] == top
    assert all(isinstance(v, int) for v in q + r)


def test_normalisation_of_rows_outside_the_kept_set_does_not_reach_kept_rows():
    mx = 40
    kept = np.array([0, 1, 13, 39, 40], np.int64)
    outside = np.array([41, 400, 1 << 40, -3], np.int64)
    both = _normalize_default_reverse(
        jnp.asarray(np.concatenate([kept, outside])), jnp.int64(mx))
    want = [MAX_NODE_SCORE - MAX_NODE_SCORE * int(v) // mx for v in kept]
    assert np.asarray(both)[:len(kept)].tolist() == want
    assert np.asarray(_normalize_default_reverse(jnp.asarray(kept), jnp.int64(0))
                      ).tolist() == [MAX_NODE_SCORE] * len(kept)


@pytest.mark.parametrize("num", [1, 2, 7, 200, 5000, 8191, 8192])
def test_wraps_are_the_remainder(num):
    rng = random.Random(f"wrap-{num}")
    xs = sorted({0, 1, num - 1, num, num + 1, 2 * num - 1}
                | {rng.randrange(0, 2 * num) for _ in range(200)})
    xs = [x for x in xs if 0 <= x < 2 * num]
    got = _wrap(jnp.asarray(np.array(xs, np.int32)), jnp.int32(num))
    assert np.asarray(got).tolist() == [x % num for x in xs]
    ys = sorted({-num, -num + 1, -1, 0, 1, num - 1}
                | {rng.randrange(-num, num) for _ in range(200)})
    ys = [y for y in ys if -num <= y < num]
    got = _unwrap(jnp.asarray(np.array(ys, np.int32)), jnp.int32(num))
    assert np.asarray(got).tolist() == [y % num for y in ys]


@pytest.mark.parametrize("NP", [1, 2, 64, 100, 8192, 8193])
def test_selection_key_unpacks_with_a_mask(NP):
    radix = _pow2(NP)                     # schedule_batch's RADIX
    assert radix >= NP and radix & (radix - 1) == 0
    rng = random.Random(f"key-{NP}")
    for _ in range(200):
        total, rot = rng.randrange(0, 100 * 1000), rng.randrange(0, NP)
        key = np.int64(total * radix + (radix - 1 - rot))
        assert radix - 1 - int(key & (radix - 1)) == rot
        # max-score-then-min-rotation is the key's order
        other = (total + 1) * radix + 0
        assert other > key
    assert int(np.int64(-1) & (radix - 1)) == radix - 1  # "none kept" stays masked by any_kept


# ---- _resource_eval whole, against the host plugins ------------------------

def _cluster():
    """Nodes of unequal allocatable, loaded unevenly with odd-sized pods."""
    from kubernetes_tpu.core import FakeClientset
    from kubernetes_tpu.models import TPUScheduler
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    rng = random.Random("resource-eval")
    cs = FakeClientset()
    s = TPUScheduler(clientset=cs, max_batch=32)
    shapes = [("500m", "1Gi", 3), ("1", "777Mi", 8), ("3", "12345Ki", 110),
              ("32", "256Gi", 110), ("96", "2Ti", 250), ("7", "1Ti", 17),
              ("250m", "513Mi", 110), ("64", "333Gi", 64)]
    n = 0
    for cpu, mem, pods in shapes:
        for _ in range(3):
            cs.create_node(make_node().name(f"n{n}").capacity(
                {"cpu": cpu, "memory": mem, "pods": pods}).obj())
            n += 1
    for i in range(90):
        cpu = rng.choice(["0", "1m", "7m", "33m", "100m", "333m", "1"])
        mem = rng.choice(["0", "1", "1023", "1Mi", "77Mi", "129Mi", "1Gi", "3333333333"])
        req = {k: v for k, v in (("cpu", cpu), ("memory", mem)) if v != "0"}
        cs.create_pod(make_pod().name(f"e{i}").req(req).node(f"n{rng.randrange(n)}").obj())
    return cs, s


@pytest.fixture(scope="module")
def cluster():
    return _cluster()


PODS = [{"cpu": "100m", "memory": "128Mi"}, {"cpu": "333m", "memory": "1000000001"},
        {"cpu": "1m"}, {"memory": "7"}, {}, {"cpu": "31", "memory": "255Gi"},
        {"cpu": "17", "memory": "3Gi"}]


@pytest.mark.parametrize("strategy", ["LeastAllocated", "MostAllocated"])
@pytest.mark.parametrize("weights", [(1, 1), (3, 100), (100, 7)])
@pytest.mark.parametrize("req", PODS, ids=[str(i) for i in range(len(PODS))])
def test_resource_eval_matches_the_host_plugins(cluster, strategy, weights, req):
    from kubernetes_tpu.api import resource as res
    from kubernetes_tpu.core.framework import CycleState
    from kubernetes_tpu.plugins.noderesources import BalancedAllocation, Fit
    from kubernetes_tpu.testing.wrappers import make_pod

    _cs, s = cluster
    pod = make_pod().name("probe").req(req).obj()
    fw = next(iter(s.profiles.values()))
    state, plan = s.build_plan(fw, pod, 32)
    f = plan.features
    nslots = f.fit_slots.shape[0]
    f = f._replace(fit_weights=jnp.asarray(
        np.array(list(weights) + [0] * (nslots - 2), np.int64)))
    fit_strategy = 0 if strategy == "LeastAllocated" else 1
    infos = s.snapshot.node_info_list
    N = len(infos)

    fit = Fit(strategy, resources=(
        {"name": res.CPU, "weight": weights[0]},
        {"name": res.MEMORY, "weight": weights[1]}))
    ba = BalancedAllocation()
    cstate = CycleState()
    fit.pre_filter(cstate, pod, None)
    ba_skipped = ba.pre_score(cstate, pod, None).is_skip()
    want = []
    for ni in infos:
        ok = fit.filter(cstate, pod, ni).is_success()
        want.append((ok, fit.score(cstate, pod, ni)[0],
                     0 if ba_skipped else ba.score(cstate, pod, ni)[0]))

    def run(rows):
        ok, sc, b = _resource_eval(
            f, fit_strategy, state.alloc_r[rows], state.alloc_pods[rows],
            state.req_r[rows], state.nonzero[rows], state.pod_count[rows])
        return np.asarray(ok).tolist(), np.asarray(sc).tolist(), np.asarray(b).tolist()

    ok, sc, b = run(slice(0, N))                   # the [N]-row callers
    assert list(zip(ok, sc, b)) == want
    assert len({w[1] for w in want}) > 3, "the cluster reads uniform: no test"
    for row in (0, 4, 11, N - 1):                  # the scan's landed row
        ok1, sc1, b1 = run(row)
        assert (ok1, sc1, b1) == want[row]


# ---- nothing in a step divides any other way -------------------------------

def _loop_primitives(jaxpr, inside=False, found=None):
    """Names of the primitives inside every scan/while body of a jaxpr."""
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        looped = inside or eqn.primitive.name in ("scan", "while")
        if inside:
            found.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _loop_primitives(inner, looped, found)
    return found


@pytest.mark.parametrize("kernel,batch,build", [
    ("scan", 8, lambda p: p.spread_constraint(
        1, "topology.kubernetes.io/zone", "DoNotSchedule", {"app": "x"})),
    ("scan-normalised", 8, lambda p: p.spread_constraint(
        1, "topology.kubernetes.io/zone", "ScheduleAnyway", {"app": "x"})
        .preferred_node_affinity(5, "topology.kubernetes.io/zone", ["z1"])),
    ("lap", 128, lambda p: p),
])
def test_no_div_or_rem_primitive_inside_a_step(cluster, kernel, batch, build):
    import jax
    from kubernetes_tpu.ops.kernel import schedule_batch
    from kubernetes_tpu.testing.wrappers import make_pod

    _cs, s = cluster
    pod = build(make_pod().name("probe").req({"cpu": "100m"}).labels({"app": "x"})).obj()
    state, plan = s.build_plan(next(iter(s.profiles.values())), pod, batch)
    assert (plan.batch_pad > 64) == (kernel == "lap")
    every_lane = kernel == "scan-normalised"   # all four kept-set normalisations
    assert plan.has_na_pref == every_lane
    jaxpr = jax.make_jaxpr(
        lambda st, f: schedule_batch.__wrapped__(
            st, f, plan.batch_pad, plan.fit_strategy, plan.vmax,
            has_pns=every_lane or plan.has_pns,
            has_ipa_base=every_lane or plan.has_ipa_base,
            anti_rowlocal=plan.anti_rowlocal, has_na_pref=plan.has_na_pref,
            port_selfblock=plan.port_selfblock, has_aux=plan.has_aux,
            has_nom=plan.has_nom))(state, plan.features)
    prims = _loop_primitives(jaxpr.jaxpr)
    assert {"ge", "sub", "shift_left"} <= prims, "found no loop body to look into"
    assert not prims & {"div", "rem"}, sorted(prims & {"div", "rem"})
