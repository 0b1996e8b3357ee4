"""The scan stops at the pods it holds (ops/kernel.py schedule_batch): a loop
of `n_active` trips returns what the fixed-length scan of `batch_pad` steps
returned, `results[:, :n]` and every field of the ScanCarry, bit for bit,
because a step past `n_active` is inert by `step`'s own masks.

The fixed-length scan lives HERE, not in the package: `_FixedLength` runs the
package's own loop body `batch_pad` times whatever `n_active` says."""

from functools import partial, wraps

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubernetes_tpu.core import FakeClientset
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.ops import kernel
from kubernetes_tpu.testing import make_node, make_pod
from kubernetes_tpu.testing.annotations import StageAnnotations

ZONE = "topology.kubernetes.io/zone"
BATCH = 64
_STATICS = ("batch_pad", "fit_strategy", "vmax", "has_pns", "has_ipa_base",
            "anti_rowlocal", "has_na_pref", "port_selfblock", "has_aux",
            "has_nom")


class _FixedLength:
    """`jax.lax` as the kernel sees it, except that a `while_loop` over the
    scan's carry (its last element is `out`, [2, batch_pad]) runs its body
    `batch_pad` times: the scan as it was before the loop was bounded."""

    def __getattr__(self, name):
        return getattr(jax.lax, name)

    @staticmethod
    def while_loop(cond, body, init):
        final, _ = jax.lax.scan(lambda c, _: (body(c), None), init, None,
                                length=init[-1].shape[-1])
        return final


def _fixed_length(fn):
    @wraps(fn)      # jit finds the static arguments by the signature
    def traced(*args, **kw):
        real, kernel.lax = kernel.lax, _FixedLength()
        try:
            return fn(*args, **kw)
        finally:
            kernel.lax = real
    return traced


def _pod(engine, name="probe"):
    b = make_pod().name(name).req({"cpu": "100m", "memory": "128Mi"}).label(
        "app", "t")
    if engine == "scan_carried":
        return b.spread_constraint(1, ZONE, "DoNotSchedule", {"app": "t"}).obj()
    return b.pod_affinity("kubernetes.io/hostname", {"app": "t"}, weight=1).obj()


def _plan(engine, batch=BATCH):
    cs = FakeClientset()
    s = TPUScheduler(clientset=cs, mesh=None, max_batch=batch)
    for i in range(40):
        cs.create_node(make_node().name(f"n{i}").capacity(
            {"cpu": 4 + i % 3, "memory": "16Gi", "pods": 110})
            .zone(f"z{i % 4}").obj())
    state, plan = s.build_plan(s.framework_for_pod(_pod(engine)), _pod(engine),
                               batch)
    assert plan.engine == engine and plan.batch_pad == batch
    return state, plan


def _statics(plan):
    return dict(has_pns=plan.has_pns, has_ipa_base=plan.has_ipa_base,
                anti_rowlocal=plan.anti_rowlocal, has_na_pref=plan.has_na_pref,
                port_selfblock=plan.port_selfblock, has_aux=plan.has_aux,
                has_nom=plan.has_nom)


@pytest.fixture(scope="module", params=["scan_carried", "scan_normalised"])
def engine(request):
    """A plan of the engine and two undonating jits of the kernel over it:
    the bounded loop as the package has it, and the fixed-length scan."""
    state, plan = _plan(request.param)
    if request.param == "scan_carried":
        assert plan.features.dns_axis.shape[0] > 0
    else:
        assert plan.features.ipa_axis.shape[0] > 0

    def call(fn):
        jitted = jax.jit(fn, static_argnames=_STATICS)
        return partial(jitted, state, plan.features, plan.batch_pad,
                       plan.fit_strategy, plan.vmax, **_statics(plan))

    bounded = call(kernel.schedule_batch.__wrapped__)
    fixed = call(_fixed_length(kernel.schedule_batch.__wrapped__))
    return bounded, fixed


def _same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and bool((a == b).all())


@pytest.mark.parametrize("n", [0, 1, 37, BATCH])
@pytest.mark.parametrize("trace", ["fresh", "chained"])
def test_the_bounded_loop_returns_what_the_fixed_length_scan_returned(
        engine, trace, n):
    bounded, fixed = engine
    carries = (None, None)
    if trace == "chained":
        # a carry that 20 landings have moved, from each side's own program
        carries = tuple(run(n_active=np.int32(20), carry_in=None)[1]
                        for run in (bounded, fixed))
    got, got_carry = bounded(n_active=np.int32(n), carry_in=carries[0])
    want, want_carry = fixed(n_active=np.int32(n), carry_in=carries[1])
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == (2, BATCH) and _same(got[:, :n], want[:, :n])
    # a pod lands at every step the loop runs (the cluster has the room), and
    # nothing is written past them (a padded step of the fixed-length scan
    # chose no row either, and wrote the unmoved start index beside it)
    assert (got[0, :n] >= 0).all() and (got[:, n:] == -1).all()
    assert (want[0, n:] == -1).all()
    for field, g, w in zip(kernel.ScanCarry._fields, got_carry, want_carry):
        assert _same(g, w), field


def test_placement_lanes_share_one_unbatched_bound():
    """`schedule_placements` vmaps the scan over candidate masks: `n_active`
    and the step counter are the same scalar in every lane, so the loop's
    predicate is not batched (a batched one would put a select over the
    whole carry into every step)."""
    state, plan = _plan("scan_carried", batch=8)
    n_rows = state.valid.shape[0]
    masks = np.zeros((4, n_rows), bool)
    masks[0, :40] = True
    masks[1, 0:40:2] = True         # two zones of four: the skew stops it
    masks[2, 3:9] = True            # lane 3 stays empty: nothing lands
    kw = dict(has_pns=plan.has_pns, has_na_pref=plan.has_na_pref,
              port_selfblock=plan.port_selfblock, has_aux=plan.has_aux)
    args = (state, plan.features, plan.batch_pad, plan.fit_strategy,
            plan.vmax, jnp.asarray(masks))
    raw = kernel.schedule_placements.__wrapped__
    got = np.asarray(kernel.schedule_placements(
        *args, n_active=np.int32(5), **kw))
    want = np.asarray(_fixed_length(raw)(*args, n_active=np.int32(5), **kw))
    assert got.shape == (4, 2, 8) and (got[:, :, :5] == want[:, :, :5]).all()
    assert (got[[0, 2], 0, :5] >= 0).all() and (got[:, :, 5:] == -1).all()
    assert (got[1, 0, :2] >= 0).all() and (got[1, 0, 2:] == -1).all()
    assert (got[3, 0] == -1).all()

    jaxpr = jax.make_jaxpr(
        lambda n: raw(*args, n_active=n, **kw))(np.int32(5))
    loops = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "while"]
    assert len(loops) == 1
    cond = loops[0].params["cond_jaxpr"].jaxpr
    # `t < n_act` and nothing else: no reduce_or over a batched predicate
    assert [e.primitive.name for e in cond.eqns] == ["lt"]
    assert all(v.aval.shape == () for e in cond.eqns for v in e.invars)


# -- the scheduler: what a dispatch counts and says of itself ---------------

def _steps(sched):
    c = sched.metrics.device_scan_steps
    return int(c.value("run")), int(c.value("skipped"))


@pytest.mark.parametrize("site", ["pods", "gang"])
def test_a_dispatch_of_5_pods_on_a_1024_wide_plan_runs_5_steps(site, monkeypatch):
    """Both dispatch sites (the pod session and the gang session) count the
    steps the scan ran and those its padded width would have cost, and say
    both on the `sched.device.dispatch` stage as it opens."""
    from kubernetes_tpu.api.types import PodGroup

    annotations = StageAnnotations()
    opened = annotations.opened
    cs = FakeClientset()
    sched = TPUScheduler(clientset=cs)
    assert sched.max_batch == 1024
    monkeypatch.setattr(sched.stages, "_annotation", annotations)
    for i in range(40):
        cs.create_node(make_node().name(f"n{i}").capacity(
            {"cpu": 8, "memory": "16Gi", "pods": 110}).zone(f"z{i % 4}").obj())
    if site == "gang":
        cs.create_pod_group(PodGroup(name="g", min_count=5))
    for i in range(5):
        pod = _pod("scan_carried", f"p{i}")
        if site == "gang":
            pod.pod_group = "g"
        cs.create_pod(pod)
    sched.run_until_idle()
    assert sched.scheduled == 5 and sched.host_path_pods == 0
    assert sched.device_batches == 1
    assert _steps(sched) == (5, 1019)
    assert 'scheduler_device_scan_steps_total{kind="skipped"} 1019.0' in (
        sched.metrics.expose())
    assert [stats for name, stats in opened
            if name == "sched.device.dispatch"] == [
        {"batch": 5, "engine": "scan_carried", "batch_pad": 1024, "steps": 5,
         "seq": 1, "inflight": 0}]


def test_the_lap_kernel_counts_no_scan_steps():
    """How many laps a lap dispatch takes only the device knows: it counts a
    batch and no steps, and its stage says `batch_pad` alone."""
    cs = FakeClientset()
    sched = TPUScheduler(clientset=cs)
    for i in range(40):
        cs.create_node(make_node().name(f"n{i}").capacity(
            {"cpu": 8, "memory": "16Gi", "pods": 110}).zone(f"z{i % 4}").obj())
    pod = make_pod().name("probe").req({"cpu": "100m"}).obj()
    _state, plan = sched.build_plan(sched.framework_for_pod(pod), pod,
                                    sched.max_batch)
    assert plan.engine == "lap"
    assert plan.dispatch_attrs(5) == {"batch": 5, "engine": "lap",
                                      "batch_pad": 1024}
    for i in range(5):
        cs.create_pod(make_pod().name(f"p{i}").req({"cpu": "100m"}).obj())
    sched.run_until_idle()
    assert sched.device_batches == 1 and sched.scheduled == 5
    assert _steps(sched) == (0, 0)
    assert "scheduler_device_scan_steps_total{" not in sched.metrics.expose()
